"""The one-scan layer runner the hybrid LM families share
(``models/lm_layers.run_layers``) against a plain Python loop over the
layers: any order of kinds, a kind with statistics among kinds without, a
second sub-layer on every layer; and what it lowers to."""

from __future__ import annotations

import collections

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mmlspark_tpu.models.lm_layers import run_layers

B, L, D = 2, 5, 8


def weights(kinds, seed: int = 0) -> dict:
    r = np.random.default_rng(seed)
    count = collections.Counter(kinds)

    def normal(*shape):
        return jnp.asarray(r.standard_normal(shape), jnp.float32)

    return {"a": normal(count["a"], D), "b": normal(count["b"], D, D) / 3,
            "c": normal(count["c"], D), "norm": normal(len(kinds), D),
            "after": normal(len(kinds), D)}


def toy(w, stats_from: str):
    """A norm, three mixers (``c`` has statistics where ``stats_from`` is
    ``mixer``) and a second sub-layer (which has them where it is
    ``after``). Every output ends in a transcendental, so no product can be
    fused into the residual sum in one program and not in the other."""

    def stats(x, at):
        return jnp.round(x[:, 0, 0] * 100).astype(jnp.int32), at + 1

    def c(at, x):
        y = jnp.sin(x + w["c"][at])
        return (y, *stats(x, at)) if stats_from == "mixer" else y

    def after(h, i):
        y = jnp.cos(h.astype(jnp.float32) * w["after"][i])
        return (y, *stats(h, i)) if stats_from == "after" else y

    mixers = {"a": lambda at, x: jnp.tanh(x * w["a"][at]),
              "b": lambda at, x: jnp.tanh(x @ w["b"][at]), "c": c}
    return (lambda h, i: h.astype(jnp.float32) * w["norm"][i]), mixers, after


NO_STATS = (jnp.zeros((B,), jnp.int32), jnp.zeros((), jnp.int32))


def split(out, none):
    return (out[0], out[1:]) if isinstance(out, tuple) else (out, none)


def loop(h, kinds, norm, mixers, after, dtype):
    """The same stack, a layer at a time in Python, ``at`` counted by
    hand; statistics where a layer has them, zeros elsewhere."""
    seen, stats = collections.Counter(), []
    for i, kind in enumerate(kinds):
        at = seen[kind]
        seen[kind] += 1
        out = mixers[kind](at, norm(h, i))
        y, s = split(out, NO_STATS)
        h = (h.astype(jnp.float32) + y).astype(dtype)
        y, s = split(after(h, i), s)
        h = (h.astype(jnp.float32) + y).astype(dtype)
        stats.append(s)
    return h, tuple(np.stack([np.asarray(s[j]) for s in stats])
                    for j in range(2))


ORDERS = ["abcaabcbba", "cccab", "bacbcaa", "abbbbc"]


@pytest.mark.parametrize("stats_from", ["mixer", "after"])
@pytest.mark.parametrize("order", ORDERS)
def test_the_runner_is_a_loop_over_the_layers_to_the_bit(order, stats_from):
    kinds = tuple(order)
    w = weights(kinds, seed=len(order))
    norm, mixers, after = toy(w, stats_from)
    h0 = jnp.asarray(np.random.default_rng(1).standard_normal((B, L, D)),
                     jnp.bfloat16)
    got_h, got = jax.jit(lambda h: run_layers(
        h, kinds, norm, mixers, jnp.bfloat16, after=after,
        no_stats=NO_STATS))(h0)
    want_h, want = loop(h0, kinds, norm, mixers, after, jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(got_h, np.float32),
                                  np.asarray(want_h, np.float32))
    for g, wnt in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), wnt)
    # the statistics land at the layers that have them: the place among
    # its kind (plus one) at a ``c`` layer, zeros at the others
    places = np.asarray(got[1])
    if stats_from == "mixer":
        where = [i for i, k in enumerate(kinds) if k == "c"]
        assert places[where].tolist() == list(range(1, len(where) + 1))
        assert not places[[i for i in range(len(kinds))
                           if i not in where]].any()
    else:
        assert places.tolist() == list(range(1, len(kinds) + 1))


def primitives(jaxpr) -> list:
    """Every primitive of a jaxpr, its sub-jaxprs' included."""
    out = []
    for eqn in jaxpr.eqns:
        out.append(eqn.primitive.name)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                if hasattr(sub, "eqns"):
                    out += primitives(getattr(sub, "jaxpr", sub))
    return out


@pytest.mark.parametrize("order,conds", [("aaaa", 0), ("cc", 0),
                                         ("abcaab", 1), ("ba", 1)])
def test_one_kind_has_no_conditional_and_more_have_one_switch(order,
                                                              conds):
    kinds = tuple(order)
    norm, mixers, after = toy(weights(kinds), "mixer")

    def run(h):
        return run_layers(h, kinds, norm, mixers, jnp.bfloat16, after=after,
                          no_stats=NO_STATS)

    h = jnp.zeros((B, L, D), jnp.bfloat16)
    names = primitives(jax.make_jaxpr(run)(h).jaxpr)
    assert names.count("scan") == 1
    assert names.count("cond") == conds
    assert (jax.jit(run).lower(h).as_text().count("stablehlo.case")
            == conds)


def test_no_statistics_anywhere_is_none():
    kinds = tuple("abba")
    norm, mixers, _ = toy(weights(kinds), "after")
    h = jnp.ones((B, L, D), jnp.bfloat16)
    out, stats = run_layers(h, kinds, norm, mixers, jnp.bfloat16)
    assert stats is None and out.shape == h.shape and out.dtype == h.dtype

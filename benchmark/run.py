#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one run. It refuses to produce a result unless JAX
finds a TPU whose ``device_kind`` is in ``peaks.json`` and as many chips
as the cell asks for. Everything of one cell is data: ``BENCHMARK.json``
names the cell's configuration and metrics, ``workloads/<cell>.json`` its
traffic, limits and driver, ``drivers/<driver>.py`` runs that kind of
traffic, ``layer_metrics/<metric>.py`` reads one per-layer metric each.
See README.md beside this file.

The last line of standard output is the result; the numbers compared for
``correct`` are the last lines of standard error and the result's last key.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()        # set-up is counted from here

import argparse                      # noqa: E402
import importlib                     # noqa: E402
import importlib.util                # noqa: E402
import json                          # noqa: E402
import os                            # noqa: E402
import sys                           # noqa: E402
import tempfile                      # noqa: E402
import threading                     # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")


class Refused(Exception):
    """This run cannot give a result (no chip, unknown cell, no program)."""


def say(msg: str) -> None:
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def load_file_module(directory: str, name: str):
    """The module in ``<directory>/<name>.py``: how a driver or a
    per-layer metric is found by its name."""
    path = os.path.join(HERE, directory, name + ".py")
    if not os.path.isfile(path):
        raise Refused(f"no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{directory}_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def seed_key(seed: int):
    """A jax key from any non-negative seed, also one past 32 bits."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed >> 31),
                              seed & 0x7FFFFFFF)


class Context:
    """What a driver is given: the cell's data and the run's arguments."""

    def __init__(self, cell: dict, config: dict, workload: dict,
                 peaks: dict | None, seed: int, seconds: float,
                 trace: bool):
        self.cell, self.config, self.workload = cell, config, workload
        self.peaks, self.seed, self.seconds = peaks, seed, seconds
        self.trace = trace
        self.say = say

    def key(self):
        return seed_key(self.seed)

    def reference(self):
        """The plain reference of the configuration's family."""
        return importlib.import_module(
            f"benchmark.reference.{self.config['family']}")


def applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name: str, manifest_path: str = MANIFEST,
              workloads_dir: str = os.path.join(HERE, "workloads")) -> tuple:
    """``(manifest, its entry of the cell, the configuration file, the
    workload file)`` of the cell called ``name``."""
    manifest = load_json(manifest_path)
    cells = {c["name"]: c for c in manifest["workloads"]}
    if name not in cells:
        raise Refused(f"unknown workload {name!r}; the manifest has "
                      f"{sorted(cells)}")
    cell = cells[name]
    files = {c["name"]: c["file"] for c in manifest["configs"]}
    return (manifest, cell, load_json(os.path.join(ROOT, files[cell["config"]])),
            load_json(os.path.join(workloads_dir, name + ".json")))


def check_device(chips: int) -> tuple[dict, dict]:
    """The device as JAX reports it and its row of ``peaks.json``; raises
    unless it is a TPU in that table with enough chips."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise Refused(f"JAX found platform {dev.platform!r}, not a TPU")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chip(s), JAX found "
                      f"{len(devices)}")
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    if dev.device_kind not in peaks:
        raise Refused(f"device kind {dev.device_kind!r} is not in "
                      f"peaks.json ({sorted(peaks)})")
    return describe_device(), peaks[dev.device_kind]


def describe_device() -> dict:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count()}


def memory_peak_bytes() -> int:
    """The peak on the fullest chip. The TPU runtime counts arrays
    (``peak_bytes_in_use``: weights, optimizer state, batches in flight)
    and the scratch it reserves for a running program
    (``peak_bytes_reserved``: activations and temporaries) apart; a cell's
    arrays are live while its program runs, so the chip's peak is their
    sum. Read before the reference runs, which would raise both."""
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use", 0)
                     + stats.get("peak_bytes_reserved", 0))
    return int(max(peaks))


class TraceSlice:
    """Profiles one slice of the window from a thread of its own: waits
    ``start_s`` into the window, starts the profiler (device planes only:
    the host's own events are millions in seconds on a host-fed cell and
    are read by nothing here), keeps it on for ``settle_s + slice_s`` and a
    margin, and stops it. ``trace_reduce`` then takes the slice on the
    device's own clock: it begins ``settle_s`` after the first device
    operation in the trace (starting the profiler stalls the host's feed
    for seconds, which a device with little work queued shows as idle time
    that no untraced run has) and lasts ``slice_s``. All of it is kept
    inside the first two thirds of the window, so that the profiler's own
    start and stop fall inside it too."""

    MARGIN_S = 1.0

    def __init__(self, spec: dict, seconds: float):
        self.start_s = min(float(spec["start_s"]), 0.1 * seconds)
        self.settle_s = min(float(spec.get("settle_s", 0.0)), 0.25 * seconds)
        self.slice_s = min(float(spec["slice_s"]), 0.15 * seconds)
        self.dir = tempfile.mkdtemp(prefix="benchmark_trace_")
        self.error: BaseException | None = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="benchmark-trace")

    def start(self) -> None:
        self._thread.start()

    def _run(self) -> None:
        import jax

        try:
            time.sleep(self.start_s)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=options)
            time.sleep(self.settle_s + self.slice_s + self.MARGIN_S)
            jax.profiler.stop_trace()
        except BaseException as e:  # noqa: BLE001 - raised by finish()
            self.error = e

    def finish(self) -> dict:
        """Wait for the profiler to stop and reduce the slice."""
        import shutil

        from benchmark import trace_reduce

        self._thread.join(timeout=120)
        try:
            if self._thread.is_alive():
                raise RuntimeError("the profiler did not stop")
            if self.error is not None:
                raise self.error
            return trace_reduce.reduce_trace(self.dir, self.settle_s,
                                             self.slice_s)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def judge(compared: dict) -> bool:
    """``compared``: ``name -> [value, limit]``; correct when every value
    is a number at or under its limit."""
    ok = bool(compared)
    for value, limit in compared.values():
        if not (isinstance(value, (int, float)) and value == value
                and value <= limit):
            ok = False
    return ok


def run(argv: list | None = None, *, device_check: bool = True,
        **where) -> dict:
    """One run; returns the result line as a dict. The keyword arguments
    (``device_check`` and ``load_cell``'s ``manifest_path`` and
    ``workloads_dir``) are for the harness's own tests, which drive tiny
    cells on the CPU; the command line cannot reach them."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        raise Refused("--seed must be >= 0 and --seconds > 0")

    if not os.path.isdir(os.path.join(ROOT, "mmlspark_tpu")):
        raise Refused(f"{ROOT} holds no mmlspark_tpu: not a checkout of "
                      "the program")
    sys.path.insert(0, ROOT)
    manifest, cell, config, workload = load_cell(args.workload, **where)

    from mmlspark_tpu.utils.jit_cache import place_compilation_cache
    cache_dir = place_compilation_cache()      # before any compile
    if device_check:
        device, peaks = check_device(cell["chips"])
    else:
        device, peaks = describe_device(), None
    say(f"{cell['name']} seed {args.seed} on {device}; compile cache "
        f"{cache_dir}; imports and device client "
        f"{time.perf_counter() - T_START:.2f} s")

    ctx = Context(cell, config, workload, peaks, args.seed, args.seconds,
                  bool(args.trace))
    driver = load_file_module("drivers", workload["driver"])
    state = driver.setup(ctx)
    setup_s = time.perf_counter() - T_START
    say(f"set-up {setup_s:.2f} s")

    tracer = None
    if ctx.trace:
        tracer = TraceSlice(workload["trace"], args.seconds)
        tracer.start()
    window = driver.measure(ctx, state)
    traced = tracer.finish() if tracer is not None else None
    device["memory_peak_bytes"] = memory_peak_bytes()
    say(f"window {window['window_s']:.3f} s, {window['attempted']} "
        f"attempted, peak {device['memory_peak_bytes'] / 1e9:.2f} GB")

    driver.release(state)
    compared = driver.check(ctx, state)

    measured = dict(window["metrics"])
    measured["setup_s"] = setup_s
    metrics = {}
    if not ctx.trace:
        for m in manifest["end_to_end"]:
            if applies(m, cell["name"]):
                metrics[m["name"]] = {"value": measured[m["name"]],
                                      "unit": m["unit"]}
    else:
        device["busy_s"] = traced["busy_s"]
        device["window_s"] = traced["window_s"]
        read_from = {"config": config, "workload": workload,
                     "peaks": peaks, "window": window, "trace": traced,
                     "chips": cell["chips"]}
        for m in manifest["per_layer"]:
            if not applies(m, cell["name"]):
                continue
            value = load_file_module("layer_metrics", m["name"]).read(
                read_from)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    result = {"correct": judge(compared),
              "attempted": window["attempted"], "failed": window["failed"],
              "metrics": metrics, "device": device}
    if traced is not None:
        result["breakdown"] = {"device_ops": traced["device_ops"],
                               "idle_gaps": traced["idle_gaps"]}
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in compared.items()}
    return result


def main(argv: list | None = None) -> int:
    try:
        result = run(argv)
    except Refused as e:
        say(f"refused: {e}")
        return 3
    for name, c in result["compared"].items():
        say(f"compared {name} = {c['value']!r} (limit {c['limit']!r})")
    say(f"correct = {result['correct']}")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

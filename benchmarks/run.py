"""Run one cell of BENCHMARK.json once and print its result line.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process that refuses anything but a TPU with the chips the cell asks
for, builds the cell's traffic from the seed (or loads what an earlier run
built), warms up the cell's shapes, measures for `--seconds`, checks what
the timed path answered against the host oracle, and prints one JSON
object as its last line: the cell's end-to-end metrics with `--trace 0`,
its per-layer metrics with `--trace 1`.

Everything that belongs to one cell is data, found by the names in
BENCHMARK.json: `configs/<configuration>.json`, `traffic/<mix>.json`,
`generators/<kind>.py`, `drivers/<kind>.py`, `layers/<metric>.py`. This
file holds no cell's name, size or shape (benchmarks/README.md).
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # `setup_s` runs from here to the first timed call

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class Refused(Exception):
    """The run cannot be a measurement; exit non-zero and print no result."""


def merge(base: dict, over: dict) -> dict:
    """`base` with `over` laid on top: a group (`block`, `verifier`) takes
    the override's keys one by one, anything deeper is replaced whole."""
    out = dict(base)
    for k, v in over.items():
        both = isinstance(v, dict) and isinstance(out.get(k), dict)
        out[k] = {**out[k], **v} if both else v
    return out


def load_spec(workload: str, rehearsal: bool = False) -> dict:
    """The cell's entry, its configuration and traffic files and the
    metrics it reports, all from BENCHMARK.json and the files it names."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json (have {sorted(cells)})")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    config["name"], traffic["name"] = cell["config"], cell["traffic"]
    if rehearsal:
        config = merge(config, config.get("rehearsal", {}))
        traffic = merge(traffic, traffic.get("rehearsal", {}))
    config.pop("rehearsal", None)
    traffic.pop("rehearsal", None)

    def listed(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    end_to_end = [m for m in bench["end_to_end"] if listed(m)]
    reported = {m["name"] for m in end_to_end}
    per_layer = [
        m for m in bench["per_layer"]
        if (workload in m["workloads"] if "workloads" in m else m["moves"] in reported)
    ]
    return {
        "cell": cell, "config": config, "traffic": traffic,
        "end_to_end": end_to_end, "per_layer": per_layer,
    }


def load_reader(metric: str):
    path = os.path.join(HERE, "layers", metric + ".py")
    spec = importlib.util.spec_from_file_location(f"benchmarks.layers.{metric}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def device_info(chips: int) -> dict:
    from benchmarks.harness import chipguard

    dev = chipguard.device_info()
    if dev["count"] < chips:
        raise Refused(f"the cell asks for {chips} chip(s), JAX reports {dev['count']}")
    dev["count"] = chips
    return dev


def memory_peak_bytes(chips: int) -> Optional[int]:
    import jax

    peaks = []
    for dev in jax.devices()[:chips]:
        stats = dev.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def build_traffic(config: dict, traffic: dict, seed: int, seconds: float):
    """(data, how, path): the mix's generator run from the seed, or what an
    earlier run of it left in the traffic cache at `path`."""
    from benchmarks.harness import trafficcache

    generator = importlib.import_module(f"benchmarks.generators.{traffic['generator']}")
    data, how = trafficcache.load_or_build(config, traffic, generator, seed, seconds)
    return data, how, trafficcache.path_for(config, traffic, generator, seed, seconds)


def build_driver(config: dict, traffic: dict, seed: int, seconds: float,
                 control: Optional[str] = None):
    """(driver, how): the mix's driver over its traffic for this seed."""
    data, how, path = build_traffic(config, traffic, seed, seconds)
    driver_mod = importlib.import_module(f"benchmarks.drivers.{traffic['driver']}")
    return driver_mod.Driver(config, traffic, data, seed, control=control,
                             schedule_path=path), how


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, dev: dict,
             control: Optional[str] = None, t_start: float = T_START) -> dict:
    """Everything after the look for a chip: traffic, set-up, the window,
    the comparison. Returns the result line as a dict."""
    from benchmarks.harness.tracer import Tracer

    driver, how = build_driver(spec["config"], spec["traffic"], seed, seconds, control)
    tracer = Tracer(
        trace, os.path.join(ROOT, ".bench_cache", "trace",
                            f"{spec['cell']['name']}.{seed}"), seconds,
    )
    try:
        driver.setup()
        setup_s = time.monotonic() - t_start
        driver.run_window(seconds, tracer)
        verdict = driver.verify()
        print(json.dumps({"compared": verdict["compared"], "problems": verdict["problems"],
                          "traffic": how, **{k: verdict[k] for k in verdict
                                             if k in ("corrupted_block", "error_frames", "shed_inputs")}}),
              flush=True)
        reduced = tracer.reduced(os.environ.get("BENCH_KEEP_TRACE"))
        device = dict(dev)
        device["memory_peak_bytes"] = memory_peak_bytes(spec["cell"]["chips"])
        metrics, breakdown = {}, None
        if trace:
            if not reduced or not reduced["busy_s"] > 0:
                raise Refused("the traced slice shows no operation on the device")
            device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
            breakdown = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
            ctx = {"cell": spec["cell"]["name"], "driver": driver.layer_context(), "trace": reduced}
            for m in spec["per_layer"]:
                value = load_reader(m["name"])(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            values = dict(driver.end_to_end(), setup_s=setup_s)
            for m in spec["end_to_end"]:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    finally:
        tracer.stop()
        driver.close()
    line = {
        "correct": verdict["correct"], "attempted": verdict["attempted"],
        "failed": verdict["failed"], "metrics": metrics, "device": device,
    }
    if breakdown:
        line["breakdown"] = breakdown
    line["detail"] = driver.detail()
    return line


def ready(workload: str):
    """The cell's spec and the device, or `Refused`: no result without the
    program in this checkout, a TPU with the chips the cell asks for, a
    device of known peaks and the native host core."""
    spec = load_spec(workload)
    try:
        import bitcoinconsensus_tpu
    except ImportError as e:
        raise Refused(f"the program is not in this checkout: {e}") from e
    if not os.path.abspath(bitcoinconsensus_tpu.__file__).startswith(ROOT + os.sep):
        raise Refused(f"bitcoinconsensus_tpu came from {bitcoinconsensus_tpu.__file__}, not this checkout")
    from benchmarks.harness import chipguard, peaks
    from bitcoinconsensus_tpu import native_bridge

    chipguard.require_tpu()
    dev = device_info(spec["cell"]["chips"])
    peaks.lookup(dev["kind"])
    if not native_bridge.available():
        raise Refused(f"the native host core did not load: {native_bridge.why_absent()}")
    return spec, dev


def main(argv=None, control: Optional[str] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec, dev = ready(args.workload)
        line = run_cell(spec, args.seed, args.seconds, bool(args.trace), dev, control=control)
    except Refused as e:
        print(f"refusing to run: {e}", file=sys.stderr)
        return 2
    if control:
        line["control"] = control
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

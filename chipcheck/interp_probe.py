"""Round one of the megatransaction's 5,569 inputs through the native
interpreter alone (no device), a call a line: wall, the bytes fed to SHA-256,
thread seconds a digest, and where the tree has them the template's counts
(`resumed`: digests started from a grid point) and the `interpret` call's
native stage clock in ms: its stages (`setup`, `workers`, `merge`) and its
fan-out's own account (`wall`, `held`, `sum`, `max`, `start_lag`, `tail`, and
`level` = max x width / sum: 1.0 when every worker was busy as long as the
slowest). usage: interp_probe.py <root> [threads ...] (default: 1
and the CPU count)"""
import importlib, json, os, resource, sys, time
root = sys.argv[1]
sys.path.insert(0, root); sys.path.insert(0, os.path.join(root, "benchmarks"))
os.chdir(root)
import run
from benchmarks.harness import trafficcache
from bitcoinconsensus_tpu import native_bridge
from bitcoinconsensus_tpu.core.flags import height_to_flags
spec = run.load_spec("worst-block-quadratic.sighash")
gen = importlib.import_module("benchmarks.generators.megatxblock")
d, how = trafficcache.load_or_build(spec["config"], spec["traffic"], gen, 3000004777, 10.0)
raw, outs = d["txs"][0]["raw"], d["txs"][0]["outs"]
flags = height_to_flags(d["height"], extended=True)
n = len(outs)
for T in [int(a) for a in sys.argv[2:]] or [1, os.cpu_count()]:
    for rep in range(6):
        # a fresh parse a call, as a connect has: the template is built in the call
        ntx = native_bridge.NativeTx(raw); ntx.set_spent_outputs(outs)
        sess = native_bridge.NativeSession()
        f0 = resource.getrusage(resource.RUSAGE_SELF)
        t0 = time.perf_counter()
        ok, err, unk, rec_idx, bounds = sess.verify_inputs_idx(
            [ntx] * n, list(range(n)), [a for a, _ in outs], [s for _, s in outs], [flags] * n, n_threads=T)
        dt = time.perf_counter() - t0
        f1 = resource.getrusage(resource.RUSAGE_SELF)
        n_bytes, thread_s = sess.sighash_work()["legacy"]
        templates = sess.sighash_templates() if hasattr(sess, "sighash_templates") else None
        stages = workers = None
        if hasattr(sess, "stages"):
            read = sess.stages()
            stages = {stage: s * 1e3 for (call, stage), (s, _) in read.stages.items() if call == "interpret"}
            workers = {stat: s * 1e3 for (call, stat), s in read.fans.items() if call == "interpret"}
            workers["level"] = workers["max"] * workers["held"] / workers["wall"] / workers["sum"]
        print(json.dumps({"root": root, "threads": T, "wall_ms": dt * 1e3, "ok": int(ok.sum()), "bytes": n_bytes,
                          "thread_s": thread_s, "ms_a_digest": thread_s / n * 1e3,
                          "mb_per_s_thread": n_bytes / thread_s / 1e6, "templates": templates,
                          "kb_per_input": n_bytes / n / 1e3, "stages_ms": stages, "fan_out_ms": workers,
                          "minflt": f1.ru_minflt - f0.ru_minflt,
                          "utime": f1.ru_utime - f0.ru_utime, "stime": f1.ru_stime - f0.ru_stime}))
        sess.release()

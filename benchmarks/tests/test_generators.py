"""Same seed, same bytes; any seed, same counts and shapes."""

import importlib
import pickle
from collections import Counter

import pytest

import run


def _build(workload, seed, seconds=4.0, rehearsal=True):
    spec = run.load_spec(workload, rehearsal=rehearsal)
    gen = importlib.import_module(f"benchmarks.generators.{spec['traffic']['generator']}")
    return spec, gen.build(spec["config"], spec["traffic"], seed, seconds)


@pytest.mark.parametrize("workload", ["tip-block.cold", "tip-block.warm", "mempool-serve.steady"])
def test_same_seed_same_bytes(workload):
    a = _build(workload, 2**31 + 5)[1]
    b = _build(workload, 2**31 + 5)[1]
    assert pickle.dumps(a) == pickle.dumps(b)
    assert pickle.dumps(a) != pickle.dumps(_build(workload, 6)[1])


def _block_shape(d):
    sizes = sorted(len(t["outs"]) for t in d["txs"])
    unseen = sorted(len(d["txs"][t]["outs"]) for t in d["unseen_txs"])
    return (d["n_inputs"], len(d["txs"]), sizes, Counter(d["kinds"]), unseen, len(d["coins"]))


def test_block_counts_do_not_depend_on_the_seed_at_full_size():
    spec, d1 = _build("tip-block.warm", 11, rehearsal=False)
    _, d2 = _build("tip-block.warm", 2**31 + 12, rehearsal=False)
    assert _block_shape(d1) == _block_shape(d2)
    assert d1["n_inputs"] == 6000 and len(d1["txs"]) == 2400
    assert Counter(d1["kinds"]) == {"p2wpkh": 3300, "p2tr": 1200, "p2pkh": 900, "p2wsh_multisig": 600}
    assert len(d1["unseen_txs"]) == 120
    assert sum(len(d1["txs"][t]["outs"]) for t in d1["unseen_txs"]) == 300
    assert d1["block"] != d2["block"] and d1["victim"] != d2["victim"]


def _stream_shape(d):
    w = d["window"]
    dues = [d["warmup_s"]] + [r["due"] for r in w["requests"]]
    gaps = sorted(b - a for a, b in zip(dues, dues[1:]))
    return (
        len(w["requests"]), sorted(len(r["rids"]) for r in w["requests"]),
        Counter(r["tenant"] for r in w["requests"]),
        sum(t["corrupted"] for t in w["truth"].values()),
        round(w["end"], 9), len(d["warm"]["requests"]),
    ), gaps


def test_stream_counts_do_not_depend_on_the_seed():
    spec = run.load_spec("mempool-serve.steady", rehearsal=True)
    spec["traffic"]["rate_tx_per_s"] = 200.0
    gen = importlib.import_module("benchmarks.generators.txstream")
    d1 = gen.build(spec["config"], spec["traffic"], 3, 5.0)
    d2 = gen.build(spec["config"], spec["traffic"], 2**31 + 4, 5.0)
    (s1, g1), (s2, g2) = _stream_shape(d1), _stream_shape(d2)
    assert s1 == s2
    assert g1 == pytest.approx(g2, abs=1e-9)  # the same gaps, in another order
    n = s1[0]
    assert n == 1000 and s1[3] == 10  # 200 tx/s for 5 s, 1 % of them corrupted
    sizes = Counter(s1[1])
    assert sizes[1] == 600 and sizes[2] == 200 and sizes[3] + sizes[4] == 100
    assert sum(v for k, v in sizes.items() if 5 <= k <= 10) == 70
    assert sum(v for k, v in sizes.items() if 11 <= k <= 50) == 30 and max(sizes) <= 50
    # arrivals of the window lie inside it
    w = d1["window"]["requests"]
    assert d1["warmup_s"] < w[0]["due"] and w[-1]["due"] <= d1["warmup_s"] + 5.0 + 1e-9

"""The benchmark's own yardstick on the CPU: trace reduction, required
work counts, traffic generation, cell lookup, and the refusal to run
without a TPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench.harness import cell as C  # noqa: E402
from bench.harness import runner  # noqa: E402
from bench.harness import trace as TR  # noqa: E402
from bench.harness import traffic as TF  # noqa: E402
from bench.harness import work as WK  # noqa: E402


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    """Runs here compile on the CPU: keep JAX's persistent cache off, so
    no test leaves cache settings or entries behind."""
    monkeypatch.setattr(runner, "use_compile_cache", lambda root: "off")


def _dev(ops, modules=()):
    def cols(evs):
        names = [e[0] for e in evs]
        s = np.array([e[1] for e in evs], float)
        return names, s, s + np.array([e[2] for e in evs], float)
    return {"id": 0, "ops": cols(ops), "modules": cols(modules)}


def small_trace():
    """Window 0-100; a call span 10-60 whose device work is 20-30 and
    35-45; a step span 70-95 with a prefill span 72-90 and work 75-85."""
    spans = [("bench.window", 0.0, 100.0), ("bench.sweep.call", 10.0, 60.0),
             ("bench.serve.step", 70.0, 95.0),
             ("bench.serve.prefill", 72.0, 90.0)]
    ops = [("fusion", 20, 6), ("fusion.1", 24, 6), ("scan", 35, 10),
           ("jit__prefill_op", 75, 10)]
    mods = [("jit__launch", 20, 25), ("jit__prefill", 75, 10)]
    return {"spans": spans, "devices": [_dev(ops, mods)]}


def test_trace_busy_union_and_window():
    rec = small_trace()
    assert TR.window(rec) == (0.0, 100.0)
    assert TR.merge(np.array([0, 2, 10]), np.array([5, 3, 12])) == \
        [(0.0, 5.0), (10.0, 12.0)]
    assert TR.busy_ns(rec) == pytest.approx(30.0)       # 10 + 10 + 10
    assert TR.busy_ns(rec, 22, 40) == pytest.approx(13.0)
    assert TR.module_ns(rec, "prefill") == (10.0, 1)
    assert TR.device_extent(rec, 10, 60) == (20.0, 45.0)
    top = dict(TR.top_ops(rec))
    assert top["scan"] == pytest.approx(10e-9)


def test_trace_idle_gaps_named_by_host_span():
    gaps = dict(TR.idle_gaps(small_trace()))
    assert gaps["host (no span)"] == pytest.approx((10 + 10 + 5) * 1e-9)
    assert gaps["bench.sweep.call.head"] == pytest.approx(10e-9)
    assert gaps["bench.sweep.call"] == pytest.approx(5e-9)
    assert gaps["bench.sweep.call.tail"] == pytest.approx(15e-9)
    assert gaps["bench.serve.prefill.head"] == pytest.approx(3e-9)
    assert gaps["bench.serve.prefill.tail"] == pytest.approx(5e-9)
    assert gaps["bench.serve.step.tail"] == pytest.approx(5e-9)
    assert gaps["bench.serve.step.head"] == pytest.approx(2e-9)


def test_sweep_call_split():
    from bench.harness.sweep_calls import split
    rec = {**small_trace(), "surface": {"calls": [{"scenarios": 4,
                                                   "packets": 100}]}}
    (p,) = split(rec)
    assert (p["prep_ns"], p["device_ns"], p["post_ns"]) == (10.0, 20.0, 15.0)


TWO_LAYER = {"hidden_size": 8, "intermediate_size": 16,
             "num_hidden_layers": 2, "num_attention_heads": 4,
             "num_key_value_heads": 2, "head_dim": 2, "vocab_size": 10,
             "torch_dtype": "bfloat16"}


def test_work_counts_match_hand_sums():
    c = TWO_LAYER
    # per layer: q 8x8, k 8x4, v 8x4, o 8x8, mlp 3 x 8x16 = 576 weights
    assert WK.layer_params(c) == 576
    # bytes: 2 x (2 layers x (576 + 16 norms + 4 qk norms) + 8 + 8 x 10)
    assert WK.weight_bytes(c) == 2 * (2 * 596 + 8 + 80)
    # one row: 3 tokens after 2 cached -> contexts 3, 4, 5 (sum 12)
    f, b = WK.prefill_work(c, [(2, 3)])
    attn = 2 * 2 * 12 * 4 * 2 * 2            # 2*2*ctx*heads*hd, 2 layers
    assert f == 2 * 576 * 2 * 3 + attn + 2 * 8 * 10
    kv_row = 2 * 2 * 2 * 2 * 2               # k+v, kv_heads, hd, bf16, layers
    assert b == WK.weight_bytes(c) + kv_row * 5 + 3 * 8 * 2
    f, b = WK.decode_work(c, [4, 0])
    assert f == 2 * (2 * 576 * 2 + 2 * 8 * 10) + 2 * 2 * 4 * 2 * 2 * (5 + 1)
    assert b == WK.weight_bytes(c) + kv_row * (5 + 1) + 2 * 8 * 2
    assert WK.prefill_work(c, [(0, 0)]) == (0.0, 0.0)
    pk = WK.peaks("TPU v5 lite")
    assert WK.roofline_s(197e12, 0, pk) == pytest.approx(1.0)
    with pytest.raises(WK.UnknownDevice):
        WK.peaks("cpu")


def test_serve_traffic_same_work_for_every_seed():
    mix = C.load_json(ROOT / "bench/traffic/chat3.json")
    a = TF.serve_requests(mix, 5, 20.0, 1000)
    b = TF.serve_requests(mix, 5, 20.0, 1000)
    c = TF.serve_requests(mix, 2 ** 31 + 17, 20.0, 1000)
    assert [(r["t"], r["tenant"], r["max_new"]) for r in a] == \
        [(r["t"], r["tenant"], r["max_new"]) for r in b]
    assert all((x["prompt"] == y["prompt"]).all() for x, y in zip(a, b))
    assert [(r["t"], r["tenant"], len(r["prompt"]), r["max_new"])
            for r in a] == [(r["t"], r["tenant"], len(r["prompt"]),
                             r["max_new"]) for r in c]
    assert not all((x["prompt"] == y["prompt"]).all() for x, y in zip(a, c))
    other = TF.serve_requests({**mix, "schedule_seed": 99}, 5, 20.0, 1000)
    assert sorted((r["tenant"], len(r["prompt"])) for r in other) == \
        sorted((r["tenant"], len(r["prompt"])) for r in a)
    assert [r["t"] for r in other] != [r["t"] for r in a]
    assert len(a) == round(mix["rate_per_s"] * 20.0)
    assert all(len(r["prompt"]) + r["max_new"] <= mix["max_total_tokens"]
               for r in a)
    assert all(r["prompt"].min() >= 1 and r["prompt"].max() < 1000 for r in a)


def test_sweep_traffic_tenants_and_seed_block():
    mix = C.load_json(ROOT / "bench/traffic/flood128-p64.json")
    ten = TF.sweep_tenants(mix)
    assert len(ten) == 128
    assert [t["name"] for t in ten[:5]] == ["rpc0", "analytics1", "mlprep2",
                                            "batch3", "analytics4"]
    assert all(t["kernel_cycle_limit"] == 20000 for t in ten[3::4])
    assert TF.replica_seeds(mix, 3) == list(range(96, 128))
    fig9 = C.load_json(ROOT / "bench/traffic/fig9-r256.json")
    assert TF.sweep_tenants(fig9)[0]["compute_per_byte"] == 1.2


def test_every_cell_resolves_to_files():
    bm = C.load_benchmark()
    for w in bm["workloads"]:
        _, cfg, traffic = C.load_cell(bm, w["name"])
        assert (ROOT / "bench/surfaces" / f"{cfg['surface']}.py").is_file()
        for m in C.per_layer(bm, w["name"]):
            assert callable(C.metric_reader(m["name"]))
        assert C.end_to_end(bm, w["name"])
    names = {m["name"] for m in bm["per_layer"]}
    assert len(names) == len(list((ROOT / "bench/metrics").glob("*.py")))


def test_run_refuses_without_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run(
        [sys.executable, str(ROOT / "bench/run.py"), "--workload",
         "sweep.fig9.r256", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120, cwd=ROOT)
    assert r.returncode == 2
    assert r.stdout.strip() == ""
    assert "not a TPU" in r.stderr


def test_run_refuses_without_benchmark_file(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    r = subprocess.run(
        [sys.executable, str(tmp_path / "bench/run.py"), "--workload",
         "sweep.fig9.r256", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, timeout=120, cwd=tmp_path,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_new_cell_and_metric_need_only_new_files(tmp_path):
    """A cell and a per-layer metric added as data files, a reader file
    and entries; no file that was there is edited."""
    import jax

    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    mix = json.loads((ROOT / "bench/traffic/fig9-r256.json").read_text())
    mix.update(replicas=4, duration_us=10.0, check_replicas=2)
    (tmp_path / "bench/traffic/fig9-tiny.json").write_text(json.dumps(mix))
    (tmp_path / "bench/metrics/sweep.calls.py").write_text(
        "def read(rec):\n    return len(rec['surface']['calls'])\n")
    bm["workloads"].append({"name": "sweep.fig9.tiny",
                            "config": "pspin-32pu-400g",
                            "traffic": "fig9-tiny", "chips": 1,
                            "why": "test"})
    bm["per_layer"].append({"name": "sweep.calls", "unit": "calls",
                            "better": "higher", "source": "host_clock",
                            "layer": "sweep host prep",
                            "moves": "sweep_scen_per_s",
                            "workloads": ["sweep.fig9.tiny"]})
    for m in bm["end_to_end"]:
        if "workloads" in m and "sweep.fig9.r256" in m["workloads"]:
            m["workloads"].append("sweep.fig9.tiny")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    out = runner.run_cell("sweep.fig9.tiny", 3, 0.5, True, t_start=0.0,
                          root=tmp_path, devices=lambda n: jax.devices()[:n])
    assert out["correct"] is True
    assert out["metrics"]["sweep.calls"]["value"] >= 1
    after = {p: p.read_bytes() for p in before}
    assert after == before

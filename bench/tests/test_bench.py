"""Tests of the benchmark's own parts, on the CPU.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests

The trace and HLO fixtures under ``bench/data`` were recorded on a TPU v5e:
a batched packed Gram of 16 blocks of (1024, 1024) f32, called twice under
the profiler, and the Pallas launches of the planned (32768, 8192) f32
packed Gram (Mosaic bodies left out).
"""

import json
import os
import re
import subprocess
import sys

import pytest

from bench import cells, peaks, trace, work

DATA = os.path.join(cells.ROOT, "bench", "data")


def _read(name):
    with open(os.path.join(DATA, name)) as f:
        return f.read()


# -- trace reduction ----------------------------------------------------------


@pytest.fixture(scope="module")
def blocks_trace():
    return trace.load(os.path.join(DATA, "blocks_16x1024.xplane.pb"))


def test_trace_load_finds_device_ops_and_host_spans(blocks_trace):
    t = blocks_trace
    assert t.devices == 1
    # two calls of one program of 39 ops each
    assert len(t.ops) == 78
    assert sum(o.name == "syrk_dual.1" for o in t.ops) == 2
    assert all(o.opcode for o in t.ops)
    assert [s.name for s in t.spans] == ["bench.call", "bench.call"]
    assert all(o.end > o.start for o in t.ops)


def test_trace_reduce_busy_is_the_union_of_op_intervals(blocks_trace):
    red = trace.reduce(blocks_trace, _read("blocks_16x1024.hlo.txt"))
    ivs = sorted((o.start, o.end) for o in blocks_trace.ops)
    merged = []
    for s, e in ivs:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    assert red.busy_s == pytest.approx(sum(e - s for s, e in merged) * 1e-9)
    # no bench.window span in this trace: the window is first op to last
    assert red.window_s == pytest.approx(
        (max(o.end for o in blocks_trace.ops) - blocks_trace.ops[0].start) * 1e-9)
    assert 0 < red.busy_s <= red.window_s
    # an explicit window clips: only ops that start inside it count
    half = (red.window[0], red.window[0] + 0.5 * (red.window[1] - red.window[0]))
    assert trace.reduce(blocks_trace, window=half).busy_s < red.busy_s


def test_trace_breakdown_names_kernels_and_labels_gaps(blocks_trace):
    hlo = _read("blocks_16x1024.hlo.txt")
    red = trace.reduce(blocks_trace, hlo)
    kernels = {l.name: l.kernel for l in work.parse_launches(hlo)}
    b = trace.breakdown(red, kernels)
    assert b["device_ops"][0][0] == "syrk_dual"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert sum(v for _, v in b["device_ops"]) == pytest.approx(red.seconds(red.ops))
    idle = sum(v for _, v in b["idle_gaps"])
    assert idle == pytest.approx(red.window_s - red.busy_s, rel=1e-6)
    assert {k for k, _ in b["idle_gaps"]} <= {"bench.call",
                                              "host.outside_bench_spans"}


def test_union_seconds():
    assert trace.union_seconds([(0, 10), (5, 20), (30, 40)]) == pytest.approx(30e-9)
    assert trace.union_seconds([]) == 0.0


# -- HLO work counter ---------------------------------------------------------


def test_work_counter_reads_the_planned_gram_launches():
    launches = work.parse_launches(_read("single_32768x8192.launches.hlo.txt"))
    kinds = {}
    for l in launches:
        kinds[l.kernel] = kinds.get(l.kernel, 0) + 1
    assert kinds == {"syrk_dual": 64, "gemm_tn": 186}
    first = launches[0]
    assert first.name == "syrk_dual.72"
    assert first.operands == (("f32", (4096, 1024)), ("f32", (4096, 1024)))
    assert first.result == (("f32", (1024, 1024)),)
    w = work.launch_work(first)
    # the symmetric half and diagonal of a (4096, 1024) Gram
    assert w["flops"] == 4096 * 1024 * 1025
    # one operand read once (passed twice), one result written
    assert w["bytes"] == 4 * (4096 * 1024 + 1024 * 1024)
    gemm = next(l for l in launches if l.kernel == "gemm_tn")
    a, b = gemm.operands[0][1], gemm.operands[1][1]
    assert work.launch_work(gemm)["flops"] == 2 * a[0] * a[1] * b[1]
    total = sum(work.launch_work(l)["flops"] for l in launches)
    # the recursion saves work against the classical m·n·(n+1)
    assert 0.5 * 32768 * 8192 * 8193 < total < 32768 * 8192 * 8193


def test_work_counter_batched_launch_and_roofline():
    (launch,) = work.parse_launches(_read("blocks_16x1024.hlo.txt"))
    w = work.launch_work(launch)
    assert w["flops"] == 16 * 1024 * 1024 * 1025
    p = peaks.peaks_for("TPU v5 lite")
    secs, bound = work.roofline_seconds(w, p)
    # a (1024, 1024) Gram does 128 flops a byte, under the v5e's ridge of
    # 197e12 / 819e9 = 240: its plain count is bound by the bytes
    assert bound == "memory" and secs == pytest.approx(w["bytes"] / 819e9)
    assert w["flops"] / p["flops"] < secs


def test_kernel_roofline_share_is_below_its_ceiling(blocks_trace):
    """A float32 kernel at HIGHEST makes six bf16 passes: against the bf16
    peak it can read at most about a sixth."""
    from bench.metrics_common import kernel_roofline
    from bench.run import Context

    hlo = _read("blocks_16x1024.hlo.txt")
    ctx = Context(reduced=trace.reduce(blocks_trace, hlo),
                  launches={l.name: l for l in work.parse_launches(hlo)},
                  peaks=peaks.peaks_for("TPU v5 lite"), inputs={})
    share = kernel_roofline(ctx, lambda k: k.startswith("syrk"))
    assert 0 < share < 100 / 6 + 1
    assert kernel_roofline(ctx, lambda k: k == "gemm_tn") is None
    idle = cells.load_metric("device.idle_share.call").read(ctx)
    assert 0 < idle < 100


def test_peaks_unknown_kind_raises():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("cpu")


# -- BENCHMARK.json resolves to files by name ---------------------------------


_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_every_workload_and_metric_resolves_to_its_files():
    bench = cells.load_benchmark()
    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        spec = cells.resolve(w["name"])
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert hasattr(spec.driver, "window") and hasattr(spec.entry, "check")
        reported = {m["name"] for m in spec.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert spec.per_layer, w["name"]
        for m in spec.per_layer:
            assert m["moves"] in reported
        for name in spec.config["limits"]:
            assert _NAME.match(name)
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert callable(cells.load_metric(m["name"]).read)
        for w in m.get("workloads", []):
            assert w in {x["name"] for x in bench["workloads"]}
    for c in bench["configs"]:
        path = os.path.join(cells.ROOT, c["file"])
        assert path.startswith(os.path.join(cells.ROOT, "bench") + os.sep)
        with open(path) as f:
            assert json.load(f)["name"] == c["name"]


def test_benchmark_names_and_units_are_legal():
    bench = cells.load_benchmark()
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    names += [w["name"] for w in bench["workloads"]]
    names += [c["name"] for c in bench["configs"]]
    assert len(names) == len(set(names))
    for n in names:
        assert _NAME.match(n), n
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for dirpath, _, files in os.walk(os.path.join(cells.ROOT, "bench")):
        for f in files:
            rel = os.path.relpath(os.path.join(dirpath, f), cells.ROOT)
            if "__pycache__" in rel:
                continue
            assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_a_new_metric_or_mix_is_found_by_name(tmp_path):
    """Files and entries alone add a cell or a metric: resolution reads
    nothing but names."""
    bench = cells.load_benchmark()
    cfg = bench["configs"][0]
    extra = dict(bench)
    extra["workloads"] = bench["workloads"] + [{
        "name": f"{cfg['name']}.tiny", "config": cfg["name"],
        "traffic": "tiny", "chips": 1, "why": "test"}]
    extra["end_to_end"] = [
        dict(m, workloads=m["workloads"] + [f"{cfg['name']}.tiny"])
        if m["name"] == "call_s" else m for m in bench["end_to_end"]]
    extra["per_layer"] = bench["per_layer"] + [{
        "name": "test.constant", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "test", "moves": "call_s"}]
    root = tmp_path
    (root / "bench" / "traffic").mkdir(parents=True)
    (root / "bench" / "metrics").mkdir(parents=True)
    (root / "bench" / "configs").mkdir(parents=True)
    for c in bench["configs"]:
        src = os.path.join(cells.ROOT, c["file"])
        (root / c["file"]).write_text(open(src).read())
    (root / "bench" / "traffic" / "tiny.json").write_text(
        json.dumps({"driver": "closed_calls", "shape": [64, 32], "pool": 1}))
    (root / "bench" / "metrics" / "test.constant.py").write_text(
        "def read(ctx):\n    return 1.0\n")
    (root / "BENCHMARK.json").write_text(json.dumps(extra))
    spec = cells.resolve(f"{cfg['name']}.tiny", root=str(root))
    assert spec.traffic["shape"] == [64, 32]
    assert "test.constant" in [m["name"] for m in spec.per_layer]
    assert cells.load_metric("test.constant", root=str(root)).read(None) == 1.0


# -- no chip, no result -------------------------------------------------------


def _run_cmd(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "gram-f32.blocks_480x1024", "--seed", str(2**33 + 1), "--seconds",
         "1", "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_run_exits_nonzero_without_a_tpu_and_prints_no_result():
    p = _run_cmd(cells.ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())

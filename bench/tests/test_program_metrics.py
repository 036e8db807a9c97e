"""The readers of the program's own records, on the CPU: the scope shares
(``core.combine_share``, ``core.pack_share``) on a synthetic trace, and the
set-up steps (``setup.*_s``) on a recorded compile-event list.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import os
import sys
import types

import pytest

from bench import cells, trace

DATA = os.path.join(cells.ROOT, "bench", "data")


def _ctx(ops, op_names, launches=()):
    """A context over device ops given as (name, seconds): back to back on
    one chip, each named by ``op_names`` (HLO op_name paths)."""
    red_ops, t = [], 0.0
    for name, sec in ops:
        red_ops.append(trace.Op(0, t, t + sec * 1e9, name, name.split(".")[0], ""))
        t += sec * 1e9
    hlo = trace.HloIndex(op_names=dict(op_names), mxu_computations=set())
    red = trace.Reduced(ops=red_ops, spans=[], window=(0.0, t), devices=1,
                        busy_s=t * 1e-9, hlo=hlo)
    return types.SimpleNamespace(reduced=red, launches={n: None for n in launches})


J = "jit(<lambda>)/ata"


@pytest.fixture
def recursion():
    ops = [("gemm_tn.1", 5.0), ("gemm_tn.2", 1.0), ("fusion.1", 1.0),
           ("fusion.2", 0.5), ("fusion.3", 0.25), ("fusion.4", 1.0),
           ("pad.1", 0.25), ("copy.1", 1.0)]
    names = {
        # a launch inside an encode scope is a launch, not an operand sum
        "gemm_tn.2": f"{J}/ata.rec/strassen.encode/kernels.gemm_tn/"
                     "jit(gemm_tn_pallas)/gemm_tn/pallas_call",
        "fusion.1": f"{J}/ata.rec/ata.rec/strassen.encode/sub",
        "fusion.2": f"{J}/ata.rec/strassen.decode/concatenate",
        "fusion.3": f"{J}/ata.rec/ata.slab_sum/add",
        "fusion.4": f"{J}/ata.pack/scatter",
        "pad.1": f"{J}/ata.pad/pad",
    }
    return _ctx(ops, names, launches=("gemm_tn.1", "gemm_tn.2"))


def test_combine_share_reads_the_sums_outside_the_launches(recursion):
    got = cells.load_metric("core.combine_share").read(recursion)
    assert got == pytest.approx(100.0 * 1.75 / 10.0)


def test_pack_share_reads_the_pad_and_the_packing(recursion):
    got = cells.load_metric("core.pack_share").read(recursion)
    assert got == pytest.approx(100.0 * 1.25 / 10.0)


def test_the_innermost_scope_decides():
    # an encode sum fused under a slab sum is the slab sum's; a pack write
    # under the level-synchronous decode is the decode's
    ctx = _ctx([("fusion.1", 1.0), ("fusion.2", 3.0)],
               {"fusion.1": f"{J}/strassen.encode/ata.slab_sum/add",
                "fusion.2": f"{J}/ata.pack/ata.decode/add"})
    assert cells.load_metric("core.combine_share").read(ctx) == pytest.approx(100.0)
    assert cells.load_metric("core.pack_share").read(ctx) is None


def test_scope_shares_read_nothing_from_a_program_without_scopes():
    """The recorded batched Gram's ops carry ``ata/scatter`` and no step
    scope (a program compiled before the scopes existed)."""
    with open(os.path.join(DATA, "blocks_16x1024.hlo.txt")) as f:
        hlo = f.read()
    red = trace.reduce(trace.load(os.path.join(DATA, "blocks_16x1024.xplane.pb")),
                       hlo)
    from bench import work

    ctx = types.SimpleNamespace(
        reduced=red, launches={l.name: l for l in work.parse_launches(hlo)})
    for name in ("core.combine_share", "core.pack_share"):
        assert cells.load_metric(name).read(ctx) is None


# -- set-up steps -------------------------------------------------------------


def _recorded():
    from repro.obs import compiles

    E = compiles.Event
    evs = [E("trace", "make", 0.0, 0.5), E("lower", "jit(make)", 0.5, 0.75),
           E("compile", "jit(make)", 0.75, 1.0),
           E("trace", "<lambda>", 2.0, 6.0), E("lower", "jit(<lambda>)", 6.0, 8.5),
           E("cache_hit", "", 9.0, 9.0), E("compile", "jit(<lambda>)", 8.5, 10.0),
           E("trace", "ref", 20.0, 20.5), E("lower", "jit(ref)", 20.5, 21.0),
           E("compile", "jit(ref)", 21.0, 25.0)]
    roots = [("solve.lstsq", 2.5, 5.5), ("ata", 3.0, 4.0)]
    return evs, roots


@pytest.mark.parametrize("metric,want", [
    ("setup.trace_s", 4.0), ("setup.lower_s", 2.5), ("setup.compile_s", 1.5)])
def test_setup_steps_read_the_cells_program(metric, want, monkeypatch):
    from repro.obs import compiles

    evs, roots = _recorded()
    real = compiles.programs
    monkeypatch.setattr(compiles, "programs", lambda: real(evs, roots))
    assert cells.load_metric(metric).read(None) == want


def test_setup_steps_of_a_jitted_program_in_this_process():
    import jax

    from repro import obs
    from repro.core.ata import ata

    obs.compiles.reset()
    obs.trace.reset()
    obs.enable()
    try:
        a = jax.jit(lambda k: jax.random.normal(k, (96, 64)))(jax.random.key(1))
        jax.jit(lambda a: ata(a, n_base=16, out="packed").blocks).lower(a).compile()
    finally:
        obs.disable()
    (prog,) = obs.compiles.programs()
    for step in ("trace", "lower", "compile"):
        got = cells.load_metric(f"setup.{step}_s").read(None)
        assert got == getattr(prog, f"{step}_s") and got > 0


def test_setup_steps_read_nothing_without_the_record(monkeypatch):
    import repro.obs

    monkeypatch.setitem(sys.modules, "repro.obs.compiles", None)
    monkeypatch.delattr(repro.obs, "compiles")
    for step in ("trace", "lower", "compile"):
        assert cells.load_metric(f"setup.{step}_s").read(None) is None

"""repro.check engine tests: every rule fires on a violating program and
stays silent on a clean planned one.

Per-rule structure (the PR's acceptance criterion): a small synthetic
program that violates the contract — the rule must produce a Finding with
eqn provenance — plus a planned program traced through the same
``trace_plan`` path CI uses, on which the rule must stay quiet. The
report/allowlist machinery and the ``python -m repro.check`` CLI JSON
contract are covered at the end. The *integration* halves (rules run
against the real solve/distributed/kernel programs, positive controls on
the real batched dispatch) live with their subjects in test_solve /
test_distributed / test_leaf_dispatch / test_kernels / test_core_ata.
"""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import check
from repro.check import rules as check_rules
from repro.tune import cost


def _art(fn, *args, label="synthetic", plan=None, hlo_text=None, **overrides):
    """Trace ``fn`` into a plan-less Artifact with override-pinned rules."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    return check.Artifact(label=label, jaxpr=jaxpr.jaxpr, plan=plan,
                          hlo_text=hlo_text, overrides=overrides)


def _violations(art, rule_id):
    return check.run(art, rules=[rule_id]).violations


# ---------------------------------------------------------------------------
# registry basics
# ---------------------------------------------------------------------------


def test_registry_ships_the_eight_rules():
    # seven rules: the test keeps the name it had when the registry held eight
    assert check.rule_ids() == sorted([
        "no-dense-square", "dot-budget",
        "launch-budget", "no-full-transpose", "acc-dtype",
        "no-vmap-of-pallas", "collective-budget",
    ])
    for rid in check.rule_ids():
        r = check.REGISTRY[rid]
        assert r.doc, f"rule {rid} has no docstring"
        assert r.severity in ("error", "warning")


def test_unknown_rule_id_raises():
    art = _art(lambda x: x + 1, jnp.zeros((2, 2)))
    with pytest.raises(KeyError, match="no-such-rule"):
        check.run(art, rules=["no-such-rule"])


# ---------------------------------------------------------------------------
# no-dense-square
# ---------------------------------------------------------------------------


def test_no_dense_square_fires_on_materialized_square():
    a = jnp.zeros((16, 8), jnp.float32)
    art = _art(lambda x: x.T @ x, a, forbidden_squares={(8, 8)})
    found = _violations(art, "no-dense-square")
    assert found and found[0].shape == (8, 8)
    assert found[0].primitive == "dot_general"
    assert found[0].eqn_index is not None
    assert "eqn#" in found[0].provenance


def test_no_dense_square_descends_nested_jaxprs():
    """The square hides inside a jit body — provenance carries the path."""
    a = jnp.zeros((16, 8), jnp.float32)
    art = _art(lambda x: jax.jit(lambda y: y.T @ y)(x), a,
               forbidden_squares={(8, 8)})
    found = _violations(art, "no-dense-square")
    # the wrapper eqn's outvar matches too; the in-body finding carries
    # the enclosing path, named by the installed JAX's jit primitive
    jit_name = jax.make_jaxpr(jax.jit(lambda y: y))(a).jaxpr.eqns[0].primitive.name
    assert any(f.path == (jit_name,) for f in found), found


def test_no_dense_square_clean_on_planned_packed_grid():
    plan = dataclasses.replace(
        cost.default_plan("ata", 192, 128, backend="cpu"),
        algorithm="strassen", n_base=32, packed_block=32, out="packed",
        use_kernels=False)
    art = check.trace_plan(plan)
    assert not _violations(art, "no-dense-square")


# ---------------------------------------------------------------------------
# dot-budget
# ---------------------------------------------------------------------------


def test_dot_budget_fires_on_count_mismatch():
    a = jnp.zeros((8, 8), jnp.float32)
    art = _art(lambda x: x @ x, a, expected_dots=2)
    found = _violations(art, "dot-budget")
    assert found and "predicts 2" in found[0].message


def test_dot_budget_clean_on_planned_unrolled_ata():
    plan = dataclasses.replace(
        cost.default_plan("ata", 192, 128, backend="cpu"),
        algorithm="strassen", leaf_dispatch="unrolled", n_base=32,
        use_kernels=False)
    art = check.trace_plan(plan)
    assert not _violations(art, "dot-budget")
    # and the closed form really is s + g
    s, g = cost._ata_leaves(192, 128, 32)
    got = sum(1 for st in art.sites()
              if st.eqn.primitive.name == "dot_general")
    assert got == s + g


# ---------------------------------------------------------------------------
# launch-budget
# ---------------------------------------------------------------------------


def _one_interpret_syrk(x):
    from repro.kernels import ops

    return ops.syrk(x, blocks=(64, 64), interpret=True)


def test_launch_budget_fires_on_count_and_ceiling():
    a = jnp.zeros((64, 64), jnp.float32)
    art = _art(_one_interpret_syrk, a, expected_launches=0,
               launch_ceiling=0)
    found = _violations(art, "launch-budget")
    # one launch vs expected 0, and 1 > ceiling 0: both findings
    assert len(found) == 2
    assert any("closed" in f.message for f in found)
    assert any("budget" in f.message for f in found)


# ---------------------------------------------------------------------------
# no-full-transpose
# ---------------------------------------------------------------------------


def test_no_full_transpose_fires_above_tile_bound():
    a = jnp.zeros((8, 16), jnp.float32)
    art = _art(lambda x: x.T, a, max_transpose_dim=4)
    found = _violations(art, "no-full-transpose")
    assert found and found[0].shape == (16, 8)
    assert found[0].primitive == "transpose"


def test_no_full_transpose_mirror_budget_consumed_once():
    a = jnp.zeros((8, 8), jnp.float32)
    # two (8, 8) mirrors against a budget of one: the second must fire
    art = _art(lambda x: x.T + x.T * 2.0, a, max_transpose_dim=4,
               mirror_budget=1, mirror_shape=(8, 8))
    assert len(_violations(art, "no-full-transpose")) == 1


def test_no_full_transpose_allows_tile_granular():
    a = jnp.zeros((4, 4), jnp.float32)
    art = _art(lambda x: x.T, a, max_transpose_dim=4)
    assert not _violations(art, "no-full-transpose")


# ---------------------------------------------------------------------------
# acc-dtype
# ---------------------------------------------------------------------------


def test_acc_dtype_fires_on_bf16_accumulation():
    a = jnp.zeros((8, 8), jnp.bfloat16)
    art = _art(lambda x, y: x @ y, a, a)
    found = _violations(art, "acc-dtype")
    assert found and "bfloat16" in found[0].message


def test_acc_dtype_clean_with_pinned_preferred_type():
    a = jnp.zeros((8, 8), jnp.bfloat16)
    art = _art(
        lambda x, y: jnp.matmul(x, y, preferred_element_type=jnp.float32),
        a, a)
    assert not _violations(art, "acc-dtype")


def test_acc_dtype_clean_on_planned_bf16_grid():
    """The satellite fix: the planned bf16 paths (CG operator, Cholesky
    Schur einsums included) all pin f32 accumulation."""
    plan = dataclasses.replace(
        cost.default_plan("ata", 192, 128, backend="cpu"),
        algorithm="strassen", leaf_dispatch="unrolled", n_base=32,
        use_kernels=False, dtype="bfloat16")
    art = check.trace_plan(plan)
    assert not _violations(art, "acc-dtype")


# ---------------------------------------------------------------------------
# no-vmap-of-pallas
# ---------------------------------------------------------------------------


def test_no_vmap_of_pallas_fires_on_vmapped_kernel():
    a = jnp.zeros((2, 64, 64), jnp.float32)
    art = _art(jax.vmap(_one_interpret_syrk), a)
    found = _violations(art, "no-vmap-of-pallas")
    assert found and "vmapped_dims" in found[0].message


def test_no_vmap_of_pallas_clean_on_native_batch_grid():
    a = jnp.zeros((2, 64, 64), jnp.float32)
    art = _art(_one_interpret_syrk, a)   # 3-D input: native leading grid
    assert not _violations(art, "no-vmap-of-pallas")


# ---------------------------------------------------------------------------
# collective-budget
# ---------------------------------------------------------------------------

_AR_HLO = "  %ar = f32[128,128]{1,0} all-reduce(%x), replica_groups={}\n"


def test_collective_budget_fires_over_budget():
    art = _art(lambda x: x, jnp.zeros((2, 2)),
               hlo_text=_AR_HLO, collective_budget_bytes=1024)
    found = _violations(art, "collective-budget")
    assert found and "65536" in found[0].message   # 128·128·4


def test_collective_budget_respects_slack_and_budget():
    art = _art(lambda x: x, jnp.zeros((2, 2)),
               hlo_text=_AR_HLO, collective_budget_bytes=65536)
    assert not _violations(art, "collective-budget")
    art2 = _art(lambda x: x, jnp.zeros((2, 2)),
                hlo_text=_AR_HLO, collective_budget_bytes=32768,
                collective_slack=2.0)
    assert not _violations(art2, "collective-budget")


def test_collective_budget_skips_without_hlo():
    art = _art(lambda x: x, jnp.zeros((2, 2)),
               collective_budget_bytes=0)
    assert not _violations(art, "collective-budget")


# ---------------------------------------------------------------------------
# report / allowlist / obs wiring
# ---------------------------------------------------------------------------


def test_allowlist_suppresses_but_keeps_auditable():
    a = jnp.zeros((16, 8), jnp.float32)
    art = _art(lambda x: x.T @ x, a, label="known:debt",
               forbidden_squares={(8, 8)})
    allow = check.Allow(rule="no-dense-square", artifact="known:*",
                        reason="legacy retrieval path, tracked in §9")
    report = check.run(art, rules=["no-dense-square"], allowlist=[allow])
    assert report.exit_code == 0 and not report.violations
    assert len(report.allowlisted) == 1
    j = report.to_json()
    assert j["counts"] == {"artifacts": 1, "findings": 0,
                           "violations": 0, "allowlisted": 1}
    assert j["allowlist"][0]["reason"].startswith("legacy")


def test_allowlist_pattern_must_match_artifact():
    a = jnp.zeros((16, 8), jnp.float32)
    art = _art(lambda x: x.T @ x, a, label="other:site",
               forbidden_squares={(8, 8)})
    allow = check.Allow(rule="no-dense-square", artifact="known:*")
    report = check.run(art, rules=["no-dense-square"], allowlist=[allow])
    assert report.exit_code == 1 and report.violations


def test_report_json_schema_and_summary():
    a = jnp.zeros((16, 8), jnp.float32)
    art = _art(lambda x: x.T @ x, a, forbidden_squares={(8, 8)})
    report = check.run(art, rules=["no-dense-square"])
    j = report.to_json()
    assert j["schema"] == check.REPORT_SCHEMA == "repro.check/v1"
    f = j["findings"][0]
    assert f["rule"] == "no-dense-square" and f["shape"] == [8, 8]
    assert f["provenance"]
    assert "no-dense-square" in report.summary()


def test_run_increments_obs_counters():
    from repro.obs import metrics

    before = metrics.get("check.violations")
    a = jnp.zeros((16, 8), jnp.float32)
    art = _art(lambda x: x.T @ x, a, forbidden_squares={(8, 8)})
    check.run(art, rules=["no-dense-square"])
    assert metrics.get("check.violations") == before + 1
    assert metrics.get("check.findings.no-dense-square") >= 1
    assert metrics.get("check.artifacts") >= 1


# ---------------------------------------------------------------------------
# harness + CLI
# ---------------------------------------------------------------------------


def test_canonical_grid_covers_the_dispatch_matrix():
    plans = check.canonical_plans()
    # 2 engines × 2 dispatches × (ata dense, ata packed, gemm) + 7 rows
    assert len(plans) == 19
    assert {p.op for p in plans} == {"ata", "gemm_tn", "solve"}
    assert {p.leaf_dispatch for p in plans if p.op == "ata"} == {
        "unrolled", "batched"}
    assert any(p.use_kernels for p in plans)
    assert any(p.dtype == "bfloat16" for p in plans)
    assert {p.method for p in plans if p.op == "solve"} == {"factor", "cg"}


def test_bfsdfs_plans_are_planner_selected():
    """The distributed sweep's BFS/DFS artifacts trace the interleaving the
    planner picked — a BFS-containing comm_schedule on a pool-divisible
    triangle — for both output modes of the harness mesh."""
    plans = check.bfsdfs_plans(2, 4)
    assert {p.out for p in plans} == {"dense", "packed"}
    for p in plans:
        assert p.comm_schedule and "B" in p.comm_schedule
        assert p.devices == 2 and p.row_devices == 4
        t = p.nb * (p.nb + 1) // 2
        assert t % (p.devices * p.row_devices) == 0


def test_cli_quick_json_smoke(tmp_path):
    out = tmp_path / "CHECK_report.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath("src")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.check", "--quick", "--json", str(out)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    j = json.loads(out.read_text())
    assert j["schema"] == "repro.check/v1"
    assert j["counts"]["violations"] == 0
    assert j["counts"]["artifacts"] == 3
    assert "repro.check:" in proc.stdout


def test_cli_list_rules():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath("src")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.check", "--list"],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0
    for rid in check.rule_ids():
        assert rid in proc.stdout

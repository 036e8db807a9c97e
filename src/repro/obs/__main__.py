"""``python -m repro.obs`` — the obs smoke run (the CI obs-smoke step).

One planned ``plan → ata → solve.lstsq`` pipeline with tracing on, then:

* assert the metrics snapshot is non-empty and schema-valid
  (``metrics.validate_snapshot``);
* assert spans exist for the steps of a forced-recursing dispatch and
  that its compiled program names its ops with the recursion's scopes;
* assert JAX's compile steps (trace, lower, compile) were recorded for
  that jitted program (``obs.compiles.programs``);
* write the snapshot to ``BENCH_obs.json`` (``--out PATH`` overrides).

Exit code 0 only if every assertion holds — CI uploads the JSON artifact.
"""

from __future__ import annotations

import sys

from repro import obs


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    out_path = "BENCH_obs.json"
    if "--out" in argv:
        i = argv.index("--out")
        out_path = argv[i + 1]

    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    obs.enable()

    import dataclasses

    import jax
    import numpy as np

    from repro import tune
    from repro.core.ata import ata
    from repro.solve.lstsq import lstsq

    m, n, r = 192, 96, 4
    rng = np.random.default_rng(0)
    a = jax.numpy.asarray(rng.standard_normal((m, n)), jax.numpy.float32)
    b = jax.numpy.asarray(rng.standard_normal((m, r)), jax.numpy.float32)

    # 1. the planner front door (plan-cache counters)
    plan = tune.plan(op="ata", m=m, n=n, dtype="float32", out="packed")

    # 2. planned ata — plus one *forced-recursing* plan so the smoke run
    # demonstrably yields spans for real recursion levels even where the
    # planner's argmin for this small shape is the single dense dot.
    gram = ata(a, out="packed")
    rec_plan = dataclasses.replace(
        plan, algorithm="strassen", n_base=32, leaf_dispatch="batched",
        source="analytic",
    )
    compiled = jax.jit(lambda a: ata(a, plan=rec_plan, out="packed")) \
        .lower(a).compile()
    gram_rec = compiled(a)
    np.testing.assert_allclose(
        np.asarray(gram.to_dense()), np.asarray(gram_rec.to_dense()),
        rtol=2e-4, atol=2e-4,
    )

    # 3. planned solve front door
    x = lstsq(a, b, ridge=1e-3)
    assert x.shape == (n, r), x.shape

    snap = obs.metrics.validate_snapshot(obs.metrics.snapshot())

    counters = snap["counters"]
    assert counters, "metrics snapshot has no counters"
    assert any(k.startswith("tune.cache.") for k in counters), (
        "no plan-cache counters in snapshot: " + ", ".join(sorted(counters))
    )
    assert any(k.startswith("dispatch.") for k in counters), (
        "no dispatch counters in snapshot: " + ", ".join(sorted(counters))
    )

    spans = snap["spans"]
    steps = {"ata.encode", "ata.decode", "ata.pack"}
    assert steps <= set(spans), "missing step spans: " + ", ".join(sorted(spans))
    assert any(k.startswith("solve.") for k in spans), sorted(spans)
    text = compiled.as_text()
    missing = sorted(s for s in steps if f"{s}/" not in text)
    assert not missing, f"compiled program lacks the scopes {missing}"

    progs = [p for p in obs.compiles.programs() if "ata" in p.roots]
    assert progs and progs[0].lower_s is not None \
        and progs[0].compile_s is not None, (
        f"no trace/lower/compile recorded for the jitted ata: {progs}"
    )

    obs.metrics.export_json(out_path)
    p = progs[0]
    print(
        f"obs smoke OK: {len(counters)} counters, {len(spans)} span names, "
        f"program {p.fun_name}: trace {p.trace_s:.3f} s, lower "
        f"{p.lower_s:.3f} s, compile {p.compile_s:.3f} s -> {out_path}"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())

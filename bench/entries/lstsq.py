"""Entry ``lstsq``: the program's planned ridge least-squares solve.

``tune.plan(op="solve", out="packed", …)`` chooses the factor path (packed
Gram → packed Cholesky → two substitutions) from the analytic model, and
the window calls ``solve.lstsq(a, b, ridge=λ, plan=plan)`` under one
``jax.jit``. (``tune.apply.build_callable`` builds the same call without a
ridge, so the ridge is passed here.) Its answer is ``x``.
"""

from __future__ import annotations

import jax

from bench.reference import lstsq as ref

__all__ = ["plan", "program", "control", "operands", "answer", "check"]


def plan(config, traffic):
    from repro import tune

    m, n = traffic["shape"]
    p = tune.plan(op="solve", m=m, n=n, k=traffic["rhs"],
                  dtype=config["dtype"], out=config["out"])
    if p.method != "factor":
        raise RuntimeError(f"planner chose {p.method!r}, not the factor path")
    return p


def program(plan, config, traffic):
    from repro.solve import lstsq

    ridge = traffic["ridge"]
    return jax.jit(lambda a, b: lstsq(a, b, ridge=ridge, plan=plan))


def control(plan, config, traffic):
    """The reference at three bf16 passes, in the program's place."""
    ridge = traffic["ridge"]
    return jax.jit(lambda a, b: ref.solve(a, b, ridge, "high"))


def operands(key, config, traffic):
    (m, n), r, dtype = traffic["shape"], traffic["rhs"], config["dtype"]

    def make(k):
        ka, kb = jax.random.split(k)
        return (jax.random.normal(ka, (m, n), dtype),
                jax.random.normal(kb, (m, r), dtype))

    return jax.jit(make)(key)


def answer(out):
    return out


def check(ops, answers, plan, config, traffic) -> dict:
    """``solve_backward_err``: the worst backward error, in float64 on the
    host, of every kept answer for the problem ``ops``."""
    a, b = ops
    g_norm = float(jax.jit(lambda a: ref.gram_norm(a, traffic["ridge"]))(a))
    problem = ref.Problem(a, b, traffic["ridge"], g_norm=g_norm)
    return {"solve_backward_err": max(problem.backward_err(x)
                                      for x in answers)}

"""Orchestration: run the shardcheck analyzers for a config, cheap first.

`run_shardcheck` is the whole pass (CLI, bench --shardcheck, tests);
`preflight` is the fail-fast subset train.py runs before committing pod
time — spec lint plus the donation/recompile hazards, which come almost
free since the step must be traced anyway. Set PICOTRON_PREFLIGHT=0 to
skip (e.g. when iterating on a config the analyzers flag intentionally).
"""

from __future__ import annotations

import os

from picotron_tpu.analysis.report import Report

ALL_CHECKS = ("spec", "source", "collectives", "variants", "donation",
              "stability")
PREFLIGHT_CHECKS = ("spec", "donation", "stability", "variants")


def run_shardcheck(cfg, *, menv=None, checks=ALL_CHECKS,
                   budget_bytes=None, source_roots=None,
                   cost_model=None) -> Report:
    """Run the requested analyzers for `cfg`; returns the merged Report.

    Host-only: the trace-time checks lower the train step on an abstract
    mesh (the caller must have provisioned enough simulated devices — see
    tools/shardcheck.py). Cheap structural checks run first so a broken
    spec is reported even when the step cannot trace at all.
    """
    from picotron_tpu.analysis.spec_lint import lint_param_specs

    rep = Report()
    spec_ok = True
    if "spec" in checks:
        spec_rep = lint_param_specs(cfg)
        spec_ok = spec_rep.ok()
        rep.extend(spec_rep)
    if "source" in checks:
        from picotron_tpu.analysis.source_lint import lint_sources

        rep.extend(lint_sources(source_roots))
    trace_checks = {"collectives", "variants", "donation",
                    "stability"} & set(checks)
    if trace_checks:
        if not spec_ok:
            # a spec the lint rejects usually cannot trace either — stop at
            # the precise structural findings instead of a partitioner
            # backtrace
            return rep
        from picotron_tpu.analysis.trace import lower_train_step

        low = lower_train_step(cfg, menv)
        if "collectives" in trace_checks:
            from picotron_tpu.analysis.collectives import audit_collectives

            rep.extend(audit_collectives(cfg, text=low.text,
                                         state=low.state,
                                         budget_bytes=budget_bytes,
                                         cost_model=cost_model))
        if "variants" in trace_checks:
            from picotron_tpu.analysis.variants import audit_variants

            rep.extend(audit_variants(cfg, low=low))
        if "donation" in trace_checks:
            from picotron_tpu.analysis.hazards import check_donation

            rep.extend(check_donation(low.lowered, low.state, low.batch))
        if "stability" in trace_checks:
            from picotron_tpu.analysis.hazards import check_state_stability

            rep.extend(check_state_stability(low.step_fn, low.state,
                                             low.batch))
    return rep


def preflight(cfg, menv=None, *, checks=PREFLIGHT_CHECKS) -> Report:
    """train.py's fail-fast pre-flight. Raises ShardcheckError on errors
    (the exception text IS the rendered report); returns the report
    otherwise. PICOTRON_PREFLIGHT=0 disables."""
    if os.environ.get("PICOTRON_PREFLIGHT", "1") == "0":
        return Report()
    rep = run_shardcheck(cfg, menv=menv, checks=checks)
    rep.raise_if_errors()
    return rep

"""Engine contract checker — static analysis over jaxpr / HLO / source.

Three inspection layers, one Finding vocabulary (DESIGN.md §Static
contracts):

  * ``jaxpr_checks`` — contracts on the TRACED train step (no execution):
    accumulator dtype (JX001), remat-policy-applied (JX002), no host
    callbacks (JX003), collective census (JX004), the pipelined 1F1B
    schedule census (JX005).
  * ``hlo_checks``   — contracts on the COMPILED step: donation aliasing
    (HLO001), unexpected all-gathers (HLO002), memory-model cross-check
    (HLO003), the one-all-reduce-per-mini-batch schedule (HLO004), the
    compiled pipelined schedule (HLO005).
  * ``lint``         — AST rules over ``src/repro`` (LINT001–LINT005),
    waivable inline with ``# repro: noqa(RULE)``.
  * ``serve_checks`` — contracts on the COMPILED serving decode step
    (engine Layer 10): KV-pool donation aliasing (SRV001) and the
    decode-peak-vs-serve-model-vs-budget band (SRV002).

``suite.run_suite`` wires them over real reduced configurations;
``python -m repro.analysis`` is the CLI/CI gate and shares the repo
exit-code contract (0 ok / 1 error / 2 budget / 3 contract violation)
with ``launch/dryrun.py``.
"""
from .findings import (EXIT_BUDGET, EXIT_CONTRACT, EXIT_ERROR,  # noqa: F401
                       EXIT_OK, Finding, Report, RULES,
                       SEVERITY_ERROR, SEVERITY_WARNING)
from .jaxpr_checks import (check_accum_dtype, check_collectives,  # noqa: F401
                           check_host_callbacks, check_pipeline_collectives,
                           check_pipelined_step, check_remat_policy,
                           check_train_step, count_primitive, iter_eqns)
from .hlo_checks import (check_aliasing,  # noqa: F401
                         check_gradient_sync, check_memory_model,
                         check_pipeline_hlo, check_unexpected_ops,
                         collective_bytes, collectives, hlo_text,
                         measured_peak_bytes, tree_bytes)
from .lint import (category_for, lint_paths, lint_repo,  # noqa: F401
                   lint_source)
from .suite import TARGETS, check_bundle, run_suite  # noqa: F401
from .serve_checks import (SERVE_TARGETS, build_decode,  # noqa: F401
                           check_decode_aliasing, check_decode_memory,
                           run_serve_suite)

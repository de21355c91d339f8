"""shardcheck — trace-time SPMD static analysis (no TPU required).

Every sharding/collective/donation decision this framework makes is
statically checkable by abstract evaluation on CPU: the PartitionSpec
pytree against the param pytree and mesh (spec_lint), the lowered step's
collective schedule (collectives), donation + recompilation hazards
(hazards), and source-level rules (source_lint). `run_shardcheck` composes
them; `preflight` is train.py's fail-fast subset; tools/shardcheck.py is
the CLI.
"""

from picotron_tpu.analysis.collectives import (  # noqa: F401
    CollectiveOp, audit_collectives, parse_collectives,
)
from picotron_tpu.analysis.cost_model import (  # noqa: F401
    Calibration, CostModel, GENERATIONS, StepCost, resolve_generation,
    spearman,
)
from picotron_tpu.analysis.hazards import (  # noqa: F401
    check_donation, check_state_stability, parse_arg_donation,
)
from picotron_tpu.analysis.report import (  # noqa: F401
    Finding, Report, ShardcheckError,
)
from picotron_tpu.analysis.runner import (  # noqa: F401
    ALL_CHECKS, PREFLIGHT_CHECKS, preflight, run_shardcheck,
)
from picotron_tpu.analysis.source_lint import (  # noqa: F401
    lint_file, lint_sources,
)
from picotron_tpu.analysis.spec_lint import (  # noqa: F401
    lint_param_specs, lint_specs,
)
from picotron_tpu.analysis.trace import lower_train_step  # noqa: F401
from picotron_tpu.analysis.variants import (  # noqa: F401
    AbstractSig, audit_feeds, audit_variants, check_engine_feed,
    prove_serve_programs, prove_train_step, signature_of,
)

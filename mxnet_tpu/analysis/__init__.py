"""Static-analysis subsystem (docs/ANALYSIS.md).

Passes over two different artifacts — program text (the HLO auditor and
the comm/memory models layered on its tables) and Python source
(the AST linter):

  - :mod:`~mxnet_tpu.analysis.hlo_audit` — structural analysis of the
    *programs* XLA lowers/compiles: op/dtype census, dot-precision
    coverage, collective inventory with replica-group spans, donation/
    aliasing coverage, host-transfer + custom-call inventory, and program
    fingerprints whose diff explains recompiles (:class:`RecompileGuard`).
  - :mod:`~mxnet_tpu.analysis.astlint` — jit-hazard lint of the *source*:
    host syncs inside compiled hot paths, Python branches on traced
    values, nondeterminism in op code, mutable default args, unlocked
    mutation of process-global registries (``tools/lint.py`` CLI,
    ``make lint``).

Everything that used to be a regex over ``as_text()`` output queries a
:class:`ProgramReport` instead.
"""
from .hlo_audit import (  # noqa: F401
    Collective,
    DonationReport,
    Fingerprint,
    Op,
    ProgramAudit,
    ProgramReport,
    RecompileGuard,
    ShardingInfo,
    ValueDef,
    audit_compiled,
    audit_lowered,
    audit_text,
    fingerprint_diff,
    parse_sharding,
)
from .memory import (  # noqa: F401
    VALIDATION_TOLERANCE,
    BufferLife,
    Materialization,
    MemoryReport,
    jax_expected_peak,
    memory_report,
)
from .comm import (  # noqa: F401
    CollectiveCost,
    CommReport,
    Reshard,
    comm_report,
    detect_accidental_reshards,
)
from .contract import (  # noqa: F401
    ContractViolation,
    check_contract,
    expected_tiles,
)
from .astlint import (  # noqa: F401
    LintRule,
    Violation,
    lint_file,
    lint_paths,
    lint_source,
    list_rules,
)

__all__ = [
    "Op", "Collective", "DonationReport", "ProgramReport", "ProgramAudit",
    "audit_text", "audit_lowered", "audit_compiled",
    "Fingerprint", "fingerprint_diff", "RecompileGuard",
    "ShardingInfo", "parse_sharding", "ValueDef",
    "MemoryReport", "BufferLife", "Materialization", "memory_report",
    "jax_expected_peak", "VALIDATION_TOLERANCE",
    "CollectiveCost", "CommReport", "Reshard", "comm_report",
    "detect_accidental_reshards",
    "ContractViolation", "check_contract", "expected_tiles",
    "LintRule", "Violation", "lint_source", "lint_file", "lint_paths",
    "list_rules",
]

"""Test infrastructure: mock sequencer sessions, seeded fuzzing,
stored-format compat matrix.

Reference analogue: packages/runtime/test-runtime-utils,
packages/test/stochastic-test-utils, packages/test/test-version-utils.
"""
from .compat import (
    CompatConfig,
    compat_matrix,
    downgrade_channel_summary,
    import_as_fresh_document,
)
from .fuzz import (
    FuzzConfig,
    record_corpus_stream,
    record_flow_stream,
    record_op_stream,
    record_sequential_stream,
    run_convergence_fuzz,
)
from .mocks import MockCollabSession

__all__ = [
    "CompatConfig",
    "FuzzConfig",
    "MockCollabSession",
    "compat_matrix",
    "downgrade_channel_summary",
    "import_as_fresh_document",
    "record_corpus_stream",
    "record_flow_stream",
    "record_op_stream",
    "record_sequential_stream",
    "run_convergence_fuzz",
]

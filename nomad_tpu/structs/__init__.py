"""Data model (reference: nomad/structs)."""

from .structs import *  # noqa: F401,F403
from .block import AllocBlock, RowMetrics  # noqa: F401
from .funcs import (  # noqa: F401
    MAX_FIT_SCORE,
    NetworkIndex,
    allocs_fit,
    comparable_used,
    score_fit,
    score_fit_binpack,
    score_fit_spread,
)
from .node_class import (  # noqa: F401
    compute_class,
    constraint_targets_unique,
    escaped_constraints,
)

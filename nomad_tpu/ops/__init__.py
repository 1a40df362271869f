"""Device kernels and the host↔device placement engine."""

from .compile_cache import place_compile_cache

place_compile_cache()      # before any kernel below can compile

from .engine import PlacementDecision, PlacementEngine, PlacementRequest  # noqa: F401
from .executor import DeviceExecutor  # noqa: F401
from .feasibility import constraint_mask, feasible_mask  # noqa: F401
from .scoring import (  # noqa: F401
    affinity_score,
    binpack_score,
    capacity_fit,
    job_anti_affinity,
    normalize_scores,
    spread_boost,
)
# reschedule penalty is computed inline in select.step (scalar prev per scan
# step); no batched helper is exported to avoid divergent duplicates.
from .select import PlacementInputs, PlacementOutputs, place, place_jit  # noqa: F401

"""Core contribution of the paper: CPAA — PageRank via Chebyshev
Polynomial approximation — plus baselines and the distributed solver."""
from repro.core.chebyshev import (
    ChebSchedule,
    beta,
    chunk_tail_ratio,
    coefficient,
    coefficients,
    default_chunk,
    err_bound,
    make_schedule,
    power_rounds_for_tolerance,
    rounds_for_tolerance,
    sigma_c,
)
from repro.core.engine import (
    BlockEllEngine,
    CooEngine,
    FusedBlockEllEngine,
    Sharded1DEngine,
    Sharded2DEngine,
    ShardedEngine,
    as_engine,
    factor_grid,
    heuristic_mode,
    select_engine,
)
from repro.core.pagerank import (
    PageRankResult,
    cpaa,
    cpaa_adaptive,
    cpaa_adaptive_fixed,
    cpaa_fixed,
    forward_push,
    monte_carlo,
    power,
    true_pagerank_dense,
)

__all__ = [
    "ChebSchedule", "beta", "chunk_tail_ratio", "coefficient", "coefficients",
    "default_chunk", "err_bound",
    "make_schedule", "power_rounds_for_tolerance", "rounds_for_tolerance",
    "sigma_c", "PageRankResult", "cpaa", "cpaa_adaptive",
    "cpaa_adaptive_fixed", "cpaa_fixed", "forward_push",
    "monte_carlo", "power", "true_pagerank_dense",
    "CooEngine", "BlockEllEngine", "FusedBlockEllEngine", "ShardedEngine",
    "Sharded1DEngine", "Sharded2DEngine", "as_engine", "factor_grid",
    "heuristic_mode", "select_engine",
]

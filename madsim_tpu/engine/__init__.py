"""madsim_tpu.engine — the batched, XLA-compiled simulation core.

This is the TPU-native inversion of the reference's architecture
(SURVEY.md §7): instead of one OS thread per seeded run, simulation state
lives in dense arrays with a leading seed axis and one compiled step
function advances every seed in lockstep. See engine/core.py for the
full design narrative and engine/rng.py for the counter-based RNG
contract.

Importing this package enables 64-bit mode in JAX: virtual time is exact
int64 nanoseconds and trace hashes are uint64 — the integer disciplines
that make cross-backend traces bit-identical. The heavy per-seed state
(node state, event kinds/args, RNG) stays 32-bit.
"""

import jax

jax.config.update("jax_enable_x64", True)

from .core import (  # noqa: E402,F401
    CAUSAL_STATE_FIELDS,
    DERIVED_STATE_FIELDS,
    POOL_INDEX_STATE_FIELDS,
    POOL_TILE_CANDIDATES,
    STORAGE_STATE_FIELDS,
    FIRST_EXT_KIND,
    FIRST_USER_KIND,
    KIND_CLOG,
    KIND_CLOG_1W,
    KIND_CLOG_NODE,
    KIND_DUP_OFF,
    KIND_DUP_ON,
    KIND_HALT,
    KIND_KILL,
    KIND_NOP,
    KIND_PAUSE,
    KIND_RESTART,
    KIND_RESUME,
    KIND_SKEW,
    KIND_SLOW_LINK,
    KIND_SYNC_LOSS,
    KIND_SYNC_OK,
    KIND_TORN_OFF,
    KIND_TORN_ON,
    KIND_UNCLOG,
    KIND_UNCLOG_1W,
    KIND_UNCLOG_NODE,
    KIND_UNSLOW,
    HALT_DONE,
    HALT_IDLE,
    HALT_RUNNING,
    HALT_TIME_LIMIT,
    MET_HALT_CODE,
    METRIC_NAMES,
    N_METRICS,
    EmitBuilder,
    Emits,
    EngineConfig,
    HandlerCtx,
    HistorySpec,
    LAT_EDGES_NS,
    LatencySpec,
    N_LAT_BUCKETS,
    PlanRows,
    RETRY_ATTEMPT_MAX,
    RETRY_ATTEMPT_SHIFT,
    RETRY_OP_MASK,
    RETRY_STATE_FIELDS,
    RetrySpec,
    MET_RETRY,
    MET_RETRY_GIVEUP,
    SimState,
    Workload,
    lat_bucket,
    lat_bucket_hi,
    lat_bucket_lo,
    ABSINT_COUNTER_MAX,
    ABSINT_HORIZON_NS,
    ABSINT_STEP_MAX,
    ColumnContract,
    StateContract,
    SLOW_MULT_MAX,
    Build,
    build_pool_index,
    check_plan_horizon,
    column_contracts,
    core_fields,
    derived_fields,
    pool_index_eligible,
    pool_tile,
    make_init,
    make_run,
    make_run_while,
    make_step,
    pack_slow_arg,
    retry_token,
    retry_token_attempt,
    retry_token_op,
    time32_eligible,
    user_kind,
)
from .compact import make_run_compacted  # noqa: E402,F401
from .verify import check_determinism, check_layouts, compare_traces  # noqa: E402,F401
from .checkpoint import load as load_checkpoint  # noqa: E402,F401
from .checkpoint import save as save_checkpoint  # noqa: E402,F401
from .search import SearchReport, make_sweep, search_seeds  # noqa: E402,F401
from .replay import ReplayEvent, format_timeline, refold, replay  # noqa: E402,F401
from .rng import (  # noqa: E402,F401
    DRAW_SPAN_MAX,
    PURPOSE_LANES,
    Draw,
    PurposeLane,
    chance_threshold,
    lane_of,
    np_threefry2x32,
    threefry2x32,
    validate_user_purposes,
)

"""Paper core: DEPOSITUM and its composite-optimization substrate."""
from repro.core.depositum import (  # noqa: F401
    DepositumConfig,
    DepositumState,
    fused_eligibility,
    init,
    step,
    local_then_comm_round,
    stationarity_metrics,
    consensus_error,
)
from repro.core.hyper import Hyper, hyper_grid, n_sweep, stack_hypers  # noqa: F401
from repro.core.prox import (  # noqa: F401
    ProxFamily,
    ProxOperator,
    get_family,
    get_prox,
    prox_apply,
    prox_gradient,
)
from repro.core.topology import (  # noqa: F401
    mixing_matrix,
    spectral_lambda,
    validate_mixing,
    delta_coefficients,
)
from repro.core.gossip import identity_mixer  # noqa: F401
from repro.core.mixing import (  # noqa: F401
    MixPlan,
    apply_mix,
    as_dense,
    plan_spectral_lambda,
    stack_mixplans,
    validate_plan,
)
from repro.core.cohort import (  # noqa: F401
    CohortSampler,
    pad_plan,
    stack_cohorts,
)
from repro.core.compression import (  # noqa: F401
    CommMemory,
    CompressionSpec,
    active_compression,
    as_mixed,
    choco_mix,
    comm_memory,
    comm_round_keys,
    compress,
    compression_of,
    pack_payload,
    stack_specs,
    unpack_payload,
    wire_mode,
)
from repro.core.staleness import (  # noqa: F401
    StalenessPolicy,
    StragglerModel,
    check_bounded_staleness,
    replay_cohorts,
    replay_staleness,
    sync_virtual_time,
)
from repro.core.schedule import (  # noqa: F401
    MixSchedule,
    ScheduleMixer,
    apply_schedule,
    as_schedule,
    as_stacked_schedule,
    round_mixer,
    schedule_round_mask,
    schedule_spectral_lambda,
    stack_schedules,
    validate_schedule,
)

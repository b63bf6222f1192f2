from .body import (  # noqa: F401
    EpochContext,
    FnListener,
    IterationBodyResult,
    IterationConfig,
    IterationListener,
    OperatorLifeCycle,
    Workset,
    active_fraction,
    with_program_key,
)
from .checkpoint import (  # noqa: F401
    CheckpointConfig,
    CheckpointManager,
    load_pytree,
    save_pytree,
)
from .core import (  # noqa: F401
    HandedOver,
    IterationResult,
    PerEpoch,
    Replayed,
    clear_programs,
    iterate,
)

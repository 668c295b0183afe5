from predictionio_tpu.utils.config import load_pio_env  # noqa: F401
from predictionio_tpu.utils.tracing import profile_to, timed  # noqa: F401
from predictionio_tpu.utils.checkpoint import (  # noqa: F401
    CheckpointStore,
    InjectedFault,
    maybe_inject,
)

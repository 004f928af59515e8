"""Utilities: metrics logging, timing, checkpointing, retry
policy, signal handling, and the trainer chaos harness."""

from .metrics import MetricLogger, ServiceCounters, StepTimer  # noqa: F401
from .retry import RetryPolicy, retrying  # noqa: F401
from .signals import PreemptionHandler, install_sigterm_handler  # noqa: F401

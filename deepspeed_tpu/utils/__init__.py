from .logging import logger, log_dist
from .timer import ThroughputTimer

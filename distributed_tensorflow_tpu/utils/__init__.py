from . import config  # noqa: F401
from . import flops  # noqa: F401
from . import multihost  # noqa: F401

"""Heat-TPU core: array API over JAX/XLA (reference ``heat/core/``)."""
import jax as _jax

# float64/int64 parity with the reference's torch semantics. TPU computes
# f32/bf16 natively; f64 arrays are supported for API parity (XLA emulates
# or the user stays in f32 for MXU speed).
_jax.config.update("jax_enable_x64", True)

from . import communication, devices, types, version
from .communication import *
from .devices import *
from .types import *
from .dndarray import *
from .factories import *
from .constants import *
from .memory import *
from .printing import *
from .stride_tricks import *
from .sanitation import *
from . import tiling
from .tiling import *
from ._operations import *
from .arithmetics import *
from .complex_math import *
from .exponential import *
from .indexing import *
from .logical import *
from .manipulations import *
from .relational import *
from .rounding import *
from .statistics import *
from .trigonometrics import *
from . import linalg
from .linalg.basics import *
from . import random
from .random import *
from . import signal
from .signal import *
from . import io
from .io import *
from . import lazy as _lazy_pkg  # installs the _operations capture hook
from .lazy import lazy, fuse, LazyDNDarray, FUSE_STATS, reset_fuse_stats
from .base import *
from .version import __version__


def __getattr__(name: str):
    # accelerator device singletons (tpu / gpu) resolve lazily in
    # heat_tpu.core.devices so importing never initializes the XLA backend
    from . import devices as _devices_mod

    if name in _devices_mod.ACCEL_NAMES:
        return getattr(_devices_mod, name)
    raise AttributeError(f"module has no attribute {name!r} (heat_tpu namespace)")

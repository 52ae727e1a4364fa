"""`mx.sym` namespace (parity: python/mxnet/symbol/__init__.py)."""
from .symbol import (Symbol, Variable, var, Group, load, load_json,
                     zeros, ones, arange)
from . import register
from .register import _gen, invoke_symbol

_g = globals()
for _name in dir(_gen):
    if not _name.startswith("__"):
        _g[_name] = getattr(_gen, _name)

# scalar/Symbol-dispatching free functions AFTER the op hoist so they
# shadow the raw generated wrappers (which don't take scalars)
from .symbol import pow, maximum, minimum, hypot  # noqa: E402

from . import graph
from .graph import GraphPlan
from . import contrib

"""Operator library: importing this package registers every operator.

Parity map (SURVEY.md §2.2): elemwise/reduce/matrix ← src/operator/tensor/,
nn ← src/operator/nn/ + legacy root ops, init/random ← init_op.cc +
src/operator/random/, optimizer ← optimizer_op.cc, sequence+RNN ←
sequence_*.cc + rnn.cc, contrib ← src/operator/contrib/.
"""
from .registry import (OP_ALIASES, OP_REGISTRY, Operator, apply_op, get_op,
                       list_ops, make_vjp, register, zero_like_grad)
from . import elemwise
from . import reduce
from . import matrix
from . import nn
from . import init_ops
from . import random_ops
from . import optimizer_ops
from . import sequence
from . import compat
from . import vision
from . import contrib
from . import flash_attention
from . import decoder
from . import control_flow
from . import custom
from . import sparse_ops

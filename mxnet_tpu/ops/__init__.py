"""Operator catalog (SURVEY.md §2 N9/N11/N12, Appendix A).

Every module registers pure-JAX ops into the shared registry; importing this
package populates the full catalog, from which ``mx.nd.*`` and ``mx.sym.*``
namespaces are generated.
"""
from . import registry
from .registry import get_op, list_ops, register

from . import elemwise      # noqa: F401
from . import reduce_ops    # noqa: F401
from . import matrix        # noqa: F401
from . import indexing      # noqa: F401
from . import init_ops      # noqa: F401
from . import nn            # noqa: F401
from . import loss          # noqa: F401
from . import random_ops    # noqa: F401
from . import linalg        # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import ctc           # noqa: F401
from . import rnn_op        # noqa: F401
from . import detection_ops  # noqa: F401
from . import warp_ops      # noqa: F401
from . import contrib_ops   # noqa: F401
from . import rcnn_ops      # noqa: F401
from . import attention     # noqa: F401
from . import ssm           # noqa: F401
from . import mamba2        # noqa: F401
from . import shortconv     # noqa: F401
from . import mla           # noqa: F401
from . import custom        # noqa: F401
from . import shape_hooks   # noqa: F401  (must come after all registrations)

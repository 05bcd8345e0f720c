"""The names a compiled step carries, and the parts it is made of.

Every scope this package writes goes through :func:`scope`, a
``jax.named_scope``: the name lands in the ``op_name`` of every operation
traced under it, forward, recomputed and backward, changes no instruction
and costs nothing at run time (``docs/timeline.md``, "The device plane").

Some of the names are **parts**: together they partition the step.  *An
operation's part is the innermost step of its ``op_name`` that is one of
the program's exported parts* (``…/hvd::moe/…/hvd::moe::experts/…`` is
``hvd::moe``'s; ``hvd::mtp/…/hvd::mla_attention/…`` is
``hvd::mla_attention``'s, what sits directly under ``hvd::mtp`` is
``hvd::mtp``'s; ``stage1/…/hvd::batch_norm/…`` is ``stage1``'s).  A model
module exports its own as a tuple ``PARTS``, the step wrapper's are
:data:`PARTS` here, and an explicit collective called outside every other
part is a part by its whole scope, ``hvd::<kind>[::<name>]``
(:data:`COLLECTIVE_PARTS`).  Sub-scopes (``hvd::moe::*``,
``hvd::mla_attention::*``, ``hvd::batch_norm``, ``hvd::sync_bn_stats``,
``reduce_gradients``, ``inner_update``) are no parts.

One trap: JAX writes the outermost scope of differentiated code into its
marker (``jvp(decoder)/…``, ``transpose(jvp(head))/…``), where no reader
that splits the path on ``/`` finds it.  So a scope that may be the
outermost one of a differentiated function gets a plain one around it
(:func:`part_scope`: ``embed``, then ``hvd::embed``).
"""

import contextlib
import sys
from typing import Optional

import jax

PREFIX = "hvd::"

#: The step wrapper's own part (``optimizer.py``: every transformation it
#: returns traces its update under it).
OPTIMIZER = PREFIX + "optimizer"
PARTS = (OPTIMIZER,)

#: The in-trace branch of each public collective (``ops/__init__.py``)
#: writes ``hvd::<kind>[::<name>]``, the label ``ops/eager.py`` gives the
#: profiler for an eager dispatch.
COLLECTIVE_PARTS = tuple(PREFIX + kind for kind in (
    "allreduce", "grouped_allreduce", "allgather", "broadcast", "alltoall",
    "reducescatter", "barrier"))


def scope(name: str):
    """``jax.named_scope(name)``: a context manager and a decorator."""
    return jax.named_scope(name)


def collective(kind: str, name: Optional[str] = None):
    """``hvd::<kind>[::<name>]`` on every operation a traced collective
    compiles to."""
    return scope(f"{PREFIX}{kind}::{name}" if name else PREFIX + kind)


@contextlib.contextmanager
def part_scope(part: str):
    """``part`` (``hvd::<name>``) under a plain scope ``<name>``, which
    takes the place in JAX's ``jvp(…)`` marker where ``part`` would be
    lost; a context manager and a decorator."""
    with scope(part[len(PREFIX):]), scope(part):
        yield


def exported_parts():
    """``(parts, collective parts)`` of the program as it is loaded: the
    step wrapper's and the ``PARTS`` of every model module imported so
    far, in that order."""
    parts = list(PARTS)
    for name, module in sorted(sys.modules.items()):
        if name.startswith(__package__ + ".models."):
            parts += [p for p in getattr(module, "PARTS", ())
                      if p not in parts]
    return tuple(parts), COLLECTIVE_PARTS

"""The names the trainer writes into a JAX profiler trace, in one place.

Device scopes (``jax.named_scope``) name the trainer's layers in the HLO
``op_name`` metadata of every operation traced under them, forward and
backward alike (``jvp(image_tower)``, ``transpose(jvp(image_tower))``).
They are compile-time metadata: they cost nothing at run time, traced or
not.  Profilers such as xprof show the path as each device operation's
framework op.

==============  =========================================================
scope           covers
==============  =========================================================
``image_tower``  patch embedding, the ViT or ResNet stack, projection
``text_tower``   token and position embedding, the text stack, pooling,
                 projection
``loss_op``      L2 normalization and the contrastive loss op with its
                 u-state rows
``optimizer``    gradient clip or norm, the optimizer update and the
                 temperature and u bookkeeping
==============  =========================================================

Host spans (``span``) mark what a host thread of the input path is doing.
They are ``jax.profiler.TraceAnnotation`` events, recorded only while a
profiler trace runs, on the same clock as the device operations:

====================  ===================================================
span                  covers
====================  ===================================================
``repro.input.wait``  the training loop blocked on the prefetch queue
``repro.input.make``  the producer thread making the next host batch
``repro.input.copy``  the producer thread's transform of it (the launcher's
                      host-to-device copy)
====================  ===================================================
"""
from __future__ import annotations

import jax

IMAGE_TOWER = "image_tower"
TEXT_TOWER = "text_tower"
LOSS_OP = "loss_op"
OPTIMIZER = "optimizer"
SCOPES = (IMAGE_TOWER, TEXT_TOWER, LOSS_OP, OPTIMIZER)

PREFIX = "repro."
INPUT_WAIT = "input.wait"
INPUT_MAKE = "input.make"
INPUT_COPY = "input.copy"


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span ``repro.<name>`` in the profiler trace."""
    return jax.profiler.TraceAnnotation(PREFIX + name)

"""Random weights from the seed, made on the device in one jitted call.

The tree is the configuration family's (``bench/families/<family>.py``
``make``), in the layout the program takes. The benchmark makes the
weights itself, so that the program and the reference both take them
from here and neither takes them from the other.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax

from bench import spec


def make_params(conf: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The weights for ``seed``, on the default device. Seeds of any
    size are folded into a 32-bit key without collisions below 2**64."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             (seed >> 32) & 0xFFFFFFFF)
    return jax.jit(functools.partial(spec.family(conf).make, conf))(key)

"""The one seam over ``jax.shard_map``.

Call sites spell ``compat.shard_map(...)`` rather than ``jax.shard_map``
so that ``analysis.shard_specs`` can swap this module attribute for a
recording wrapper and see every shard_map the sharded plugins build.
"""
from __future__ import annotations

import jax

shard_map = jax.shard_map

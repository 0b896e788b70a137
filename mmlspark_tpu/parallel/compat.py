"""The one import site for ``shard_map`` and ``axis_size``.

Every shard_map call site in the framework routes through the two names
below (lint-enforced — graftlint rejects bare ``jax.shard_map(`` anywhere
else), so the day jax moves either symbol again is a one-file change. The
installed jax (pinned in pyproject.toml) ships both under their modern
names; nothing here adapts to older releases.
"""

from __future__ import annotations

from jax import shard_map  # noqa: F401 — re-exported funnel
from jax.lax import axis_size  # noqa: F401 — re-exported funnel

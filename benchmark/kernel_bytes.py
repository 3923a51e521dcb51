"""What the algorithm has to read for one request, from the request alone.

A Count tree reads each of its leaf rows once: one bit per column, so
``width / 8`` bytes per shard, over the deployment's real shards (954 at
1B columns, not the 1,024 of the program's pow2 bucket: padding is the
implementation's). The output is a few bytes and is not counted. Identical
requests that the program serves with one launch are still separate
answers, each with its own bytes.
"""

from __future__ import annotations

import re

_LEAF = re.compile(r"\bRow\(")


def leaves(pql: str) -> int:
    return len(_LEAF.findall(pql))


def request_bytes(pql: str, config: dict) -> int:
    """HBM bytes a Count tree needs on this deployment."""
    width = 1 << int(config["shard_width_exp"])
    return leaves(pql) * int(config["shards"]) * (width // 8)

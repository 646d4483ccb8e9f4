#!/usr/bin/env python3
"""``scope_shares.py`` with the scopes of layers that choose keys and route to
experts ahead of its own: ``sparse_attention``, ``key_select``, ``indexer``,
``experts``, ``router`` (``ddw_tpu/ops/indexed_attention.py``,
``ddw_tpu/models/moe.py``). Same arguments, same output.

    python3 benchmark/tools/scope_shares_layers.py DIR [--steps 8] [--top 12]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.tools import scope_shares                      # noqa: E402

# innermost first: the scores a tile is chosen by are computed inside the
# attention path's own scope
scope_shares.ORDER = ("key_select", "indexer", "sparse_attention", "router",
                      "experts") + scope_shares.ORDER

if __name__ == "__main__":
    sys.exit(scope_shares.main())

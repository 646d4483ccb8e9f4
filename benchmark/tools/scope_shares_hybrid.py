#!/usr/bin/env python3
"""``scope_shares.py`` with the scopes of a model that has one mixer a layer
ahead of its own: the Mamba-2 mixer's ``ssm_scan``, ``ssm_conv``,
``ssm_gate_norm`` and ``ssm_proj`` (``ddw_tpu/models/mamba.py``), the routed
layer's ``shared_expert``, ``experts`` and ``router``
(``ddw_tpu/models/moe.py``). Same arguments, same output.

    python3 benchmark/tools/scope_shares_hybrid.py DIR [--steps 8] [--top 12]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.tools import scope_shares                      # noqa: E402

scope_shares.ORDER = ("ssm_scan", "ssm_conv", "ssm_gate_norm", "ssm_proj",
                      "shared_expert", "router", "experts"
                      ) + scope_shares.ORDER

if __name__ == "__main__":
    sys.exit(scope_shares.main())

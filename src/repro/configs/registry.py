"""Import every arch module to populate the registry."""
from . import (granite_8b, minitron_8b, mistral_large_123b,
               granite_moe_3b_a800m, llama4_maverick_400b_a17b,
               gcn_cora, pna, gat_cora, gat_arxiv, nequip, wide_deep)

ALL_ARCHS = ["granite-8b", "minitron-8b", "mistral-large-123b",
             "granite-moe-3b-a800m", "llama4-maverick-400b-a17b",
             "gcn-cora", "pna", "gat-cora", "gat-arxiv", "nequip",
             "wide-deep"]

"""CPU tests of the benchmark harness, at the programs' reduced sizes."""
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# the widths `ModelConfig.reduced()` gives, written as configuration files
REDUCED = {"num_hidden_layers": 2, "hidden_size": 64,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "head_dim": 16, "intermediate_size": 128, "vocab_size": 256,
           "vocab_chunk": 64}


def reduced_arch(name: str) -> dict:
    arch = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    arch.update(REDUCED)
    return arch

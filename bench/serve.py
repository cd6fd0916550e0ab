"""The system under test, built from a configuration file and a traffic mix.

``model_config`` turns ``bench/configs/<name>.json`` (published keys under
their published names, plus a ``program`` group) into the program's
``ModelConfig``: the file, not the program's preset, sets every size.
``geometry`` fixes the compiled step geometry from the mix's declared
maxima, so every seed of a cell hits the same compiled programs.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

# published key -> ModelConfig field
_WIDTHS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}


def model_config(config: Dict):
    from repro.configs import get_config
    prog = config["program"]
    base = get_config(prog["arch"])
    sizes = {field: config[key] for key, field in _WIDTHS.items()
             if key in config}
    if "head_dim" not in config:
        sizes["head_dim"] = config["hidden_size"] // \
            config["num_attention_heads"]
    quoka = dataclasses.replace(
        base.quoka, chunk_size=int(prog["chunk_size"]),
        budget=int(prog["budget"]), n_queries=int(prog["n_queries"]),
        keep_first=int(prog["keep_first"]), method=prog["method"])
    return dataclasses.replace(base, dtype=prog["dtype"], quoka=quoka,
                               **sizes)


def geometry(config: Dict, traffic: Dict) -> Dict:
    """Compiled step geometry from the declared maxima of the mix."""
    from repro.serving.pool import max_blocks_bound
    prog = config["program"]
    chunk = int(prog["chunk_size"])
    block = chunk
    align = 1 if prog["method"] == "full" else chunk
    p_max, o_max = int(traffic["prompt"]["max"]), int(traffic["output"]["max"])
    max_nb = max_blocks_bound(p_max, o_max, chunk, block, align=align)
    decode_rows = int(traffic["decode_rows"])
    max_prefill_tokens = int(traffic["max_prefill_tokens"])
    return dict(block_size=block, max_nb=max_nb,
                prefill_rows=max(1, max_prefill_tokens // chunk),
                decode_rows=decode_rows, num_blocks=decode_rows * max_nb,
                max_prefill_tokens=max_prefill_tokens,
                capacity=max_nb * block)

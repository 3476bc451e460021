"""The benchmark's view of the program under test: its model config built
from a configuration file, and small jitted reductions of its state."""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Configuration-file keys -> the program's ModelConfig fields.
_FIELDS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
           "num_attention_heads": "num_heads",
           "num_key_value_heads": "num_kv_heads",
           "intermediate_size": "d_ff", "vocab_size": "vocab_size",
           "sliding_window": "sliding_window", "rope_theta": "rope_theta",
           "rms_norm_eps": "norm_eps"}


def model_config(cfg: dict):
    """The program's ModelConfig for configuration file ``cfg``: its
    registry entry with every size the file states, and its layer
    pattern as the repeating block unit."""
    from repro.configs import base
    over = {f: cfg[k] for k, f in _FIELDS.items() if k in cfg}
    if "block_pattern" in cfg:
        over["block_unit"] = tuple(cfg["block_pattern"])
    if cfg["family"] == "xlstm":
        over.update(num_heads=cfg["num_heads"], num_kv_heads=cfg["num_heads"],
                    lstm_heads=cfg["num_heads"])
    mc = base.get_config(cfg["registry"]).with_overrides(**over)
    if mc.dtype != cfg["dtype"] or mc.tie_embeddings != cfg[
            "tie_word_embeddings"]:
        raise ValueError(f"{cfg['name']}: the program computes in "
                         f"{mc.dtype} with tied={mc.tie_embeddings}, the "
                         f"configuration file states otherwise")
    return mc


@jax.jit
def leaf_norms(tree) -> jax.Array:
    """(N, L): each worker's norm of each leaf (leading axis = worker)."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)).reshape(x.shape[0], -1), axis=-1))
        for x in jax.tree_util.tree_leaves(tree)], axis=1)

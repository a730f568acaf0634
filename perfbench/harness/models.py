"""From a configuration file's sizes to the program's model objects."""


def transformer_config(cfg: dict, **overrides):
    """``TransformerConfig`` of a GPT-2-shaped configuration file (the
    source's key names)."""
    import jax.numpy as jnp

    from horovod_tpu.models import TransformerConfig

    sizes = dict(
        vocab_size=cfg["vocab_size"], num_layers=cfg["n_layer"],
        d_model=cfg["n_embd"], num_heads=cfg["n_head"],
        head_dim=cfg["n_embd"] // cfg["n_head"],
        mlp_ratio=cfg["n_inner"] // cfg["n_embd"],
        max_seq_len=cfg["n_positions"],
        dtype=jnp.dtype(cfg["activation_dtype"]))
    sizes.update(overrides)
    return TransformerConfig(**sizes)

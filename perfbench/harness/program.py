"""The program's model configuration from a configuration file, and the
check that the benchmark's state has the program's layout."""
from __future__ import annotations


def model_config(c: dict):
    """The program's ModelConfig for configuration file ``c``: the registry's
    architecture ``c["arch"]`` with every size the file states, so that what
    runs is what the file says."""
    from repro.configs.registry import get_config

    cfg = get_config(c["arch"]).replace(
        d_model=c["hidden_size"], d_ff=c["intermediate_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        num_layers=c["num_hidden_layers"], vocab_size=c["vocab_size"],
        norm_eps=c["rms_norm_eps"], rope_theta=c["rope_theta"],
        tie_embeddings=c["tie_word_embeddings"])
    if (cfg.act, cfg.dtype, cfg.param_dtype) != (
            c["hidden_act"], c["compute_dtype"], c["param_dtype"]):
        raise ValueError(f"{c['arch']}: act {cfg.act}, dtype {cfg.dtype}, "
                         f"param dtype {cfg.param_dtype} differ from the "
                         f"configuration file")
    return cfg


def check_tree(made, like) -> None:
    """``made`` (from ``harness.state``) must have the program's structure,
    shapes and dtypes."""
    import jax

    if jax.tree.structure(made) != jax.tree.structure(like):
        raise ValueError("the benchmark's state differs in structure from "
                         "the program's")
    for a, b in zip(jax.tree.leaves(made), jax.tree.leaves(like)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"leaf {a.shape} {a.dtype} != program's "
                             f"{b.shape} {b.dtype}")

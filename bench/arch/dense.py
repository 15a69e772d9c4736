"""The dense decoder LM: full causal attention in every layer, a SwiGLU
MLP and an untied head (``bench/weights.py``, ``bench/reference.py``,
``bench/roofline.py``).  A configuration without an ``"arch"`` key is
this one.  Every function is the benchmark's own, bound here unchanged,
so the cells on it read the numbers they read before architectures were
looked up by name."""

from bench import reference as REF
from bench import roofline as RL
from bench import weights as W

dims = W.dims
layout = W.layout
logits_rows = REF.logits_rows
decode_attention = RL.decode_attention
prefill_attention = RL.prefill_attention
matmul_params = RL.matmul_params
token_flops = RL.token_flops
chunk_flops = RL.chunk_flops


def program_fields(model: dict, as_run: dict) -> dict:
    """The program's ModelConfig fields, as the file states them."""
    m = dims(model)
    return {
        "n_layers": m["L"], "d_model": m["D"], "n_heads": m["H"],
        "n_kv_heads": m["Hkv"], "head_dim": m["Dh"], "d_ff": m["F"],
        "vocab": m["V"], "rope_theta": float(as_run["rope_theta"]),
        "norm_eps": float(as_run["norm_eps"]), "mlp": "swiglu",
        "layer_pattern": ("global",), "tie_embeddings": False,
    }

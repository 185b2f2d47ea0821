"""A toy ``sdar_moe`` for the CPU tests: the configuration as
``benchmark/reference/sdar.py`` reads it, the reference's weights in the tree
the program takes, and the program's config object for a sampler's settings."""

from benchmark import lib
from horovod_tpu.models import block_diffusion_moe as bd

ref = lib.load_module("reference", "sdar")
family = lib.load_module("families", "sdar_serve")

TINY = {"hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 8, "moe_intermediate_size": 16, "num_experts": 8,
        "num_experts_per_tok": 2, "num_hidden_layers": 3, "vocab_size": 64,
        "rope_theta": 1e4, "rms_norm_eps": 1e-6, "torch_dtype": "float32",
        "mlp_only_layers": [], "decoder_sparse_step": 1,
        "tie_word_embeddings": False, "block_length": 4, "mask_token_id": 63,
        # the toy's 32 wide states keep the scale the sister toys have: SOME
        # below is read from these weights
        "embedding_std": 1.0}
STATIC, DYNAMIC = bd.STATIC, bd.DYNAMIC
#: a threshold that some of the toy's blocks clear and some do not (its
#: confidences lie between 0.05 and 0.2, the median near 0.1)
SOME = 0.1


def toy(block: int = 4, seed: int = 5) -> tuple:
    """``(reference config, reference weights, the program's tree)``."""
    cfg = dict(TINY, block_length=block)
    w = ref.make_weights(cfg, seed)
    top = w["top"]
    return cfg, w, {"embed": top["embed"], "layers": tuple(w["layers"]),
                    "final_norm": top["final_norm"],
                    "lm_head": top["lm_head"]}


def sampler(steps: int, remasking: str = STATIC,
            threshold: float = 0.9) -> dict:
    return {"denoising_steps": steps, "remasking": remasking,
            "confidence_threshold": threshold}


def model_config(cfg: dict, s: dict, max_len: int = 64):
    """The program's config object, as the benchmark's family makes it."""
    return family.model_config(cfg, s, max_len)

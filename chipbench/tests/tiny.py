"""Reduced configurations and cells for the CPU tests."""
import copy

from chipbench import bench

MODELS = {
    "llama": {"name": "tiny-llama", "family": "dense", "block_kind": "attn",
              "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
              "head_dim": 16, "d_ff": 128, "vocab_size": 512,
              "mlp_kind": "dense", "tie_embeddings": True,
              "rope_theta": 10000.0, "norm_eps": 1e-5, "dtype": "bfloat16"},
    "mamba1": {"name": "tiny-mamba", "family": "ssm", "block_kind": "mamba1",
               "n_layers": 2, "d_model": 64, "n_heads": 0, "n_kv_heads": 0,
               "head_dim": 0, "d_ff": 0, "vocab_size": 512,
               "mlp_kind": "none", "ssm_state": 16, "d_inner": 128,
               "conv_width": 4, "tie_embeddings": False, "norm_eps": 1e-5,
               "mixer_rms": False, "mixer_rms_eps": 1e-6,
               "dtype": "bfloat16"},
}

TRAFFIC = {"generator": "openloop", "arrivals": {"kind": "exp_quantiles"},
           "prompt": {"median": 40, "sigma": 0.8, "min": 8, "max": 120},
           "output": {"median": 16, "sigma": 0.7, "min": 4, "max": 48}}


def spec(kind: str, rate: float = 4.0, dtype: str = "bfloat16",
         limit: float = 0.25) -> dict:
    """A whole cell at a CPU size: the real metric list, a tiny model."""
    model = dict(MODELS[kind], dtype=dtype)
    b = bench.benchmark()
    return {"name": f"tiny-{kind}", "chips": 1,
            "config": {"name": f"tiny-{kind}", "kind": kind, "model": model,
                       "engine": {"max_rows": 4, "max_len": 256,
                                  "num_blocks": 64, "prefill_chunk": 32,
                                  "decode_steps": 8},
                       "check": {"logit_gap_max": limit}},
            "traffic": copy.deepcopy(TRAFFIC),
            "cell": {"rate_rps": rate},
            "end_to_end": b["end_to_end"], "per_layer": b["per_layer"]}

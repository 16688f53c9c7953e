"""CPU rehearsal of ``train-lfm2-1chip`` at a tiny size, through the same
``run_cell`` the command line uses (``rehearse_mixed.py`` does
``train-laguna-1chip``):

    python3 benchmark/tests/rehearse_hybrid.py [trace]

What it prints is a count or a CPU timing and never a device number.
"""
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmark import run as R  # noqa: E402

# Lfm2Config.tiny() with half of its experts held
TINY = {"module": "lfm2", "preset": "tiny", "experts_held": [0, 4],
        "dtype": "float32", "param_dtype": "float32"}
SIZES = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
         "num_hidden_layers": 5, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "num_experts": 4,
         "num_experts_per_tok": 2, "moe_intermediate_size": 32,
         "conv_L_cache": 3, "model_config": TINY,
         "held": {"layer_kinds": ["conv+dense", "attn", "conv", "conv",
                                  "conv"],
                  "num_experts_routed_over": 8}}
trace = bool(int(sys.argv[1])) if len(sys.argv) > 1 else False
ov = {"platform": "cpu", "devices": 1, "model_config": TINY, "config": SIZES,
      "scaling": {"num_workers": 1, "use_tpu": False,
                  "chips_per_worker": None},
      "jax_config": {"platform": "cpu", "cpu_devices_per_worker": 1},
      "traffic": {"batch": 2, "seq": 32, "host_batches": 8,
                  "warmup_steps": 2, "trace_from_step": 1, "trace_steps": 2,
                  "check": {"loss_tolerance": 1e-4,
                            "router_logit_rms_tolerance": 1e-4,
                            "router_logit_max_tolerance": 1e-3,
                            "differing_choice_share_tolerance": 0.0,
                            "choice_regret_tolerance": 0.0,
                            "biased_choice_regret_tolerance": 1e-6,
                            "token_nll_rms_tolerance": 1e-4,
                            "token_nll_max_tolerance": 1e-3,
                            "router_bias_tolerance": 0.0,
                            "gradient_gap_tolerance": dict.fromkeys(
                                ("conv_dense", "attn_moe", "conv_moe",
                                 "top"), 1e-4)}}}
print(json.dumps(R.run_cell("train-lfm2-1chip", 2 ** 31 + 5, 2, trace,
                            ov))[:3000])

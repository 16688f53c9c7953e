"""CPU rehearsal of ``train-deepseek-v2-1chip`` at a tiny size, through
the same ``run_cell`` the command line uses (``rehearse_mixed.py`` does
``train-laguna-1chip``):

    python3 benchmark/tests/rehearse_latent.py [trace]

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

# DeepseekV2Config.tiny() with half its heads and a quarter of its experts
TINY = {"module": "deepseek_v2", "preset": "tiny", "num_heads": 2,
        "heads_of": 4, "experts_held": [4, 4], "dtype": "float32",
        "param_dtype": "float32"}
SIZES = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
         "num_hidden_layers": 3, "num_attention_heads": 2,
         "q_lora_rank": 32, "kv_lora_rank": 24, "qk_nope_head_dim": 16,
         "qk_rope_head_dim": 8, "v_head_dim": 12, "head_dim": 24,
         "n_routed_experts": 4, "n_shared_experts": 2,
         "num_experts_per_tok": 3, "moe_intermediate_size": 32,
         "held": {"layer_kinds": ["mla+dense", "mla", "mla"],
                  "num_experts_routed_over": 16}}
trace = bool(int(sys.argv[1])) if len(sys.argv) > 1 else False
kinds = ("mla_dense", "mla_moe", "top")
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
                            "token_nll_rms_tolerance": 1e-4,
                            "token_nll_max_tolerance": 1e-3,
                            "gradient_gap_tolerance": dict.fromkeys(
                                kinds, 1e-4),
                            "first_step_moment_tolerance": dict.fromkeys(
                                kinds, 1e-4),
                            "first_step_param_tolerance": 1e-6}}}
print(json.dumps(R.run_cell("train-deepseek-v2-1chip", 2 ** 31 + 5, 2, trace,
                            ov))[:3000])

"""CPU rehearsal of ``train-dots3-1chip`` at a tiny size, through the same
``run_cell`` the command line uses (``rehearse_latent.py`` does
``train-deepseek-v2-1chip``):

    python3 benchmark/tests/rehearse_sparse.py [trace]

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

# Dots3Config.tiny() with half its heads and a quarter of its experts
TINY = {"module": "dots3", "preset": "tiny", "num_heads": 2, "heads_of": 4,
        "swa_num_heads": 1, "swa_heads_of": 2, "experts_held": [4, 4],
        "dtype": "float32", "param_dtype": "float32"}
SIZES = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
         "num_hidden_layers": 4, "num_attention_heads": 2,
         "swa_num_attention_heads": 1, "q_lora_rank": 32, "kv_lora_rank": 16,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 12,
         "swa_q_lora_rank": 32, "swa_kv_lora_rank": 32,
         "swa_qk_nope_head_dim": 24, "swa_qk_rope_head_dim": 8,
         "swa_v_head_dim": 12, "sliding_window_size": 5, "head_dim": 24,
         "index_n_heads": 4, "index_head_dim": 16, "index_topk": 8,
         "n_routed_experts": 4, "n_shared_experts": 1,
         "num_experts_per_tok": 3, "moe_intermediate_size": 32,
         "held": {"layer_kinds": ["full+dense", "full", "sliding", "sliding"],
                  "num_experts_routed_over": 16}}
trace = bool(int(sys.argv[1])) if len(sys.argv) > 1 else False
kinds = ("full_dense", "full_moe", "sliding_moe", "top")
ov = {"platform": "cpu", "devices": 1, "model_config": TINY, "config": SIZES,
      "scaling": {"num_workers": 1, "use_tpu": False,
                  "chips_per_worker": None},
      "jax_config": {"platform": "cpu", "cpu_devices_per_worker": 1},
      "traffic": {"batch": 2, "seq": 48, "host_batches": 8,
                  "warmup_steps": 2, "trace_steps": 2,
                  "check": {"loss_tolerance": 1e-4,
                            "index_loss_tolerance": 1e-4,
                            "router_logit_rms_tolerance": 1e-4,
                            "router_logit_max_tolerance": 1e-3,
                            "differing_choice_share_tolerance": 0.0,
                            "choice_regret_tolerance": 0.0,
                            "index_score_rms_tolerance": 1e-5,
                            "index_score_max_tolerance": 1e-4,
                            "differing_key_share_tolerance": 0.0,
                            "key_regret_tolerance": 0.0,
                            "key_count_tolerance": 0.0,
                            "band_tolerance": 1e-4,
                            "token_nll_rms_tolerance": 1e-4,
                            "token_nll_max_tolerance": 1e-3,
                            "gradient_gap_tolerance": dict.fromkeys(
                                kinds, 1e-3),
                            "first_step_moment_tolerance": dict.fromkeys(
                                kinds, 1e-3),
                            "first_step_param_tolerance": 1e-6,
                            "router_bias_tolerance": 0.0}}}
print(json.dumps(R.run_cell("train-dots3-1chip", 2 ** 31 + 5, 2, trace,
                            ov))[:3000])

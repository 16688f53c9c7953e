"""CPU rehearsal of ``train-ling3-flash-1chip`` at a tiny size, through the
same ``run_cell`` the command line uses (``rehearse_delta_moe.py`` does
``train-qwen3-next-1chip``):

    python3 benchmark/tests/rehearse_kda_moe.py [trace]

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

# Ling3Config.tiny() holding experts 4-7 of 16 (group 1 of 4): a dense KDA
# layer, a routed one, the latent layer and one more routed KDA layer
TINY = {"module": "ling3", "preset": "tiny", "dtype": "float32",
        "param_dtype": "float32", "experts_held": [4, 4]}
KINDS = ["kda+dense", "kda+moe", "mla+moe", "kda+moe"]
SIZES = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
         "num_hidden_layers": 4, "num_attention_heads": 4, "head_dim": 16,
         "kv_lora_rank": 24, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
         "v_head_dim": 12, "kda_lower_bound": -5, "num_experts": 4,
         "num_experts_per_tok": 3, "moe_intermediate_size": 32,
         "moe_shared_expert_intermediate_size": 32, "model_config": TINY,
         "held": {"layer_kinds": KINDS, "num_experts_routed_over": 16}}
trace = bool(int(sys.argv[1])) if len(sys.argv) > 1 else False
kinds = ("kda+dense", "kda+moe", "mla+moe", "top")
CHECK = {"loss_tolerance": 1e-4, "token_nll_rms_tolerance": 1e-4,
         "token_nll_max_tolerance": 1e-3, "state_abs_max_tolerance": 1e-4,
         "state_head_gap_tolerance": 1e-4,
         "router_logit_rms_tolerance": 1e-4,
         "router_logit_max_tolerance": 1e-3,
         "differing_choice_share_tolerance": 0.0,
         "choice_regret_tolerance": 0.0,
         "own_choice_regret_tolerance": 1e-6,
         "own_weight_gap_tolerance": 1e-6, "router_bias_tolerance": 0.0,
         "first_step_moment_tolerance": dict.fromkeys(kinds, 1e-4),
         "first_step_param_tolerance": 0.0,
         "gradient_gap_tolerance": dict.fromkeys(kinds, 1e-4),
         "gradient_gap_median_tolerance": dict.fromkeys(kinds, 1e-4)}
ov = {"platform": "cpu", "devices": 1, "model_config": TINY, "config": SIZES,
      "scaling": {"num_workers": 1, "use_tpu": False,
                  "chips_per_worker": None},
      "jax_config": {"platform": "cpu", "cpu_devices_per_worker": 1},
      "traffic": {"batch": 1, "seq": 32, "host_batches": 8,
                  "warmup_steps": 2, "trace_steps": 2,
                  "check": CHECK}}
if __name__ == "__main__":
    print(json.dumps(R.run_cell("train-ling3-flash-1chip", 2 ** 31 + 5, 2,
                                trace, ov))[:3000])

"""CPU rehearsal of ``train-qwen3-next-1chip`` at a tiny size, through the
same ``run_cell`` the command line uses (``rehearse_delta.py`` does
``train-olmo-hybrid-1chip``):

    python3 benchmark/tests/rehearse_delta_moe.py [trace]

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

# Qwen3NextConfig.tiny() holding experts 4-7 of 16: three delta-rule layers
# of 2 key heads under 4 value heads and one gated full layer
TINY = {"module": "qwen3_next", "preset": "tiny", "dtype": "float32",
        "param_dtype": "float32", "experts_held": [4, 4]}
SIZES = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
         "num_hidden_layers": 4, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16,
         "linear_num_key_heads": 2, "linear_num_value_heads": 4,
         "linear_key_head_dim": 16, "linear_value_head_dim": 16,
         "linear_conv_kernel_dim": 4, "num_experts": 4,
         "num_experts_per_tok": 4, "moe_intermediate_size": 32,
         "shared_expert_intermediate_size": 32, "model_config": TINY,
         "held": {"layer_kinds": ["linear", "linear", "linear", "full"],
                  "num_experts_routed_over": 16}}
trace = bool(int(sys.argv[1])) if len(sys.argv) > 1 else False
kinds = ("linear", "full", "top")
ov = {"platform": "cpu", "devices": 1, "model_config": TINY, "config": SIZES,
      "scaling": {"num_workers": 1, "use_tpu": False,
                  "chips_per_worker": None},
      "jax_config": {"platform": "cpu", "cpu_devices_per_worker": 1},
      "traffic": {"batch": 1, "seq": 32, "host_batches": 8,
                  "warmup_steps": 2, "trace_from_step": 1, "trace_steps": 2,
                  "check": {"loss_tolerance": 1e-4,
                            "token_nll_rms_tolerance": 1e-4,
                            "token_nll_max_tolerance": 1e-3,
                            "state_abs_max_tolerance": 1e-4,
                            "state_head_gap_tolerance": 1e-4,
                            "router_logit_rms_tolerance": 1e-4,
                            "router_logit_max_tolerance": 1e-3,
                            "differing_choice_share_tolerance": 0.0,
                            "choice_regret_tolerance": 0.0,
                            "first_step_moment_tolerance": dict.fromkeys(
                                kinds, 1e-4),
                            "first_step_param_tolerance": 0.0,
                            "gradient_gap_tolerance": dict.fromkeys(
                                kinds, 1e-4)}}}
print(json.dumps(R.run_cell("train-qwen3-next-1chip", 2 ** 31 + 5, 2, trace,
                            ov))[:3000])

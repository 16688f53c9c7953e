"""CPU rehearsal of ``train-nemotron3-super-1chip`` at a tiny size, through
the same ``run_cell`` the command line uses (``rehearse_delta_moe.py`` does
``train-qwen3-next-1chip``):

    python3 benchmark/tests/rehearse_scan_moe.py [trace]

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

# Nemotron_hConfig.tiny() holding experts 4-7 of 16: two scans of 8 heads in
# 2 groups, two mixtures in a latent of 32, one attention, the module's *E
TINY = {"module": "nemotron_h", "preset": "tiny", "dtype": "float32",
        "param_dtype": "float32", "experts_held": [4, 4]}
SIZES = {"vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 5,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "mamba_num_heads": 8, "mamba_head_dim": 16, "ssm_state_size": 16,
         "n_groups": 2, "conv_kernel": 4, "chunk_size": 8,
         "n_routed_experts": 4, "num_experts_per_tok": 4,
         "moe_latent_size": 32, "moe_intermediate_size": 48,
         "moe_shared_expert_intermediate_size": 96, "model_config": TINY,
         "held": {"layer_kinds": ["mamba", "moe", "mamba", "attention",
                                  "moe"],
                  "mtp_layer_kinds": ["attention", "moe"],
                  "num_experts_routed_over": 16}}
KINDS = ("mamba", "attention", "moe", "mtp_attention", "mtp_moe", "top")
CHECK = {"loss_tolerance": 1e-4, "mtp_loss_tolerance": 1e-4,
         "token_nll_rms_tolerance": 1e-4, "token_nll_max_tolerance": 1e-3,
         "mtp_nll_rms_tolerance": 1e-4, "mtp_nll_max_tolerance": 1e-3,
         "state_abs_max_tolerance": 1e-4, "state_head_gap_tolerance": 1e-4,
         "router_logit_rms_tolerance": 1e-4,
         "router_logit_max_tolerance": 1e-3,
         "differing_choice_share_tolerance": 0.0,
         "choice_regret_tolerance": 0.0,
         "biased_choice_regret_tolerance": 1e-6,
         "router_bias_tolerance": 0.0,
         "first_step_moment_tolerance": dict.fromkeys(KINDS, 1e-4),
         "first_step_param_tolerance": 0.0,
         "gradient_gap_tolerance": dict.fromkeys(KINDS, 1e-4)}
OVERRIDES = {"platform": "cpu", "devices": 1, "model_config": TINY,
             "config": SIZES,
             "scaling": {"num_workers": 1, "use_tpu": False,
                         "chips_per_worker": None},
             "jax_config": {"platform": "cpu", "cpu_devices_per_worker": 1},
             "traffic": {"batch": 1, "seq": 32, "host_batches": 8,
                         "warmup_steps": 2, "trace_from_step": 1,
                         "trace_steps": 2, "check": CHECK}}
if __name__ == "__main__":
    trace = bool(int(sys.argv[1])) if len(sys.argv) > 1 else False
    print(json.dumps(R.run_cell("train-nemotron3-super-1chip", 2 ** 31 + 5,
                                2, trace, OVERRIDES))[:3000])

"""CPU rehearsal of ``train-granite-1chip`` at a tiny size, through the
same ``run_cell`` the command line uses (``rehearse_hybrid.py`` does
``train-lfm2-1chip``):

    python3 benchmark/tests/rehearse_scan.py [trace]

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

# GraniteConfig.tiny(): three Mamba-2 layers around one attention layer
TINY = {"module": "granite", "preset": "tiny", "dtype": "float32",
        "param_dtype": "float32"}
SIZES = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
         "shared_intermediate_size": 128, "num_hidden_layers": 4,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
         "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_chunk_size": 8,
         "model_config": TINY,
         "held": {"layer_kinds": ["mamba", "mamba", "attention", "mamba"]}}
trace = bool(int(sys.argv[1])) if len(sys.argv) > 1 else False
ov = {"platform": "cpu", "devices": 1, "model_config": TINY, "config": SIZES,
      "scaling": {"num_workers": 1, "use_tpu": False,
                  "chips_per_worker": None},
      "jax_config": {"platform": "cpu", "cpu_devices_per_worker": 1},
      "traffic": {"batch": 2, "seq": 32, "host_batches": 8,
                  "warmup_steps": 2, "trace_from_step": 1, "trace_steps": 2,
                  "check": {"loss_tolerance": 1e-4,
                            "token_nll_rms_tolerance": 1e-4,
                            "token_nll_max_tolerance": 1e-3,
                            "state_abs_max_tolerance": 1e-4,
                            "state_head_gap_tolerance": 1e-4,
                            "first_step_moment_tolerance": dict.fromkeys(
                                ("mamba", "attention", "top"), 1e-4),
                            "first_step_param_tolerance": 0.0,
                            "gradient_gap_tolerance": dict.fromkeys(
                                ("mamba", "attention", "top"), 1e-4)}}}
print(json.dumps(R.run_cell("train-granite-1chip", 2 ** 31 + 5, 2, trace,
                            ov))[:3000])

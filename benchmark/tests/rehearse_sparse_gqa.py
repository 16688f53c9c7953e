"""CPU rehearsal of ``train-keye-vl2-1chip`` at a tiny size, through the
same ``run_cell`` the command line uses (``rehearse_sparse.py`` does
``train-dots3-1chip``):

    python3 benchmark/tests/rehearse_sparse_gqa.py [trace]

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

# KeyeVL2Config.tiny() with a quarter of its experts
TINY = {"module": "keye_vl2", "preset": "tiny", "experts_held": [4, 4],
        "dtype": "float32", "param_dtype": "float32"}
SIZES = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
         "num_hidden_layers": 3, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16,
         "sa_config": {"indexer_num_heads": 4, "indexer_head_dim": 8,
                       "indexer_num_kv_heads": 1, "topk": 8},
         "index_topk": 8, "num_experts": 4, "num_local_experts": 16,
         "num_experts_per_tok": 4, "moe_intermediate_size": 32,
         "held": {"layer_kinds": ["sparse_gqa+moe"] * 3,
                  "num_experts_routed_over": 16}}
KINDS = ("layer_0", "layer_1", "top")
CHECK = {"loss_tolerance": 1e-4, "index_loss_tolerance": 1e-4,
         "index_loss_layer_tolerance": 1e-4, "balance_tolerance": 1e-4,
         "router_logit_rms_tolerance": 1e-4,
         "router_logit_max_tolerance": 1e-3,
         "differing_choice_share_tolerance": 0.0,
         "choice_regret_tolerance": 0.0,
         "index_score_rms_tolerance": 1e-5,
         "index_score_max_tolerance": 1e-4,
         "differing_key_share_tolerance": 0.0, "key_regret_tolerance": 0.0,
         "key_count_tolerance": 0.0, "token_nll_rms_tolerance": 1e-4,
         "token_nll_max_tolerance": 1e-3,
         "gradient_gap_tolerance": dict.fromkeys(KINDS, 1e-3),
         "first_step_moment_tolerance": dict.fromkeys(KINDS, 1e-3),
         "first_step_param_tolerance": 1e-6}
TRAFFIC = {"batch": 1, "seq": 48, "host_batches": 8, "warmup_steps": 2,
           "trace_steps": 2, "text_run": [2, 6],
           "grids": [[2, 2], [2, 3], [3, 3]], "check": CHECK}

if __name__ == "__main__":
    trace = bool(int(sys.argv[1])) if len(sys.argv) > 1 else False
    ov = {"platform": "cpu", "devices": 1, "model_config": TINY,
          "config": SIZES,
          "scaling": {"num_workers": 1, "use_tpu": False,
                      "chips_per_worker": None},
          "jax_config": {"platform": "cpu", "cpu_devices_per_worker": 1},
          "traffic": TRAFFIC}
    print(json.dumps(R.run_cell("train-keye-vl2-1chip", 2 ** 31 + 5, 2,
                                trace, ov))[:3000])

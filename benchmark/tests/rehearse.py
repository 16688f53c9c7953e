"""CPU rehearsal of the four cells at a tiny size, through the same
``run_cell`` the command line uses, with the sizes decided here:

    python3 benchmark/tests/rehearse.py chat|docqa|1chip|fsdp4 [trace]

Finds wrong paths, arguments and control flow before any chip time. What
it prints is a count or a CPU timing and never a device number.
"""
import os, sys, json
os.environ["JAX_PLATFORMS"] = "cpu"
# XLA:CPU programs loaded back from the persistent cache hang in the
# virtual devices' collectives; a rehearsal compiles afresh
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
from benchmark import run as R
TINY = {"preset": "tiny", "vocab_size": 256, "num_heads": 4, "num_kv_heads": 2, "head_dim": 16,
        "dtype": "float32", "param_dtype": "float32", "tie_embeddings": True, "attn_qkv_bias": True}
which = sys.argv[1]; trace = bool(int(sys.argv[2])) if len(sys.argv) > 2 else False
tiny_model = {"config": {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
              "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "tie_word_embeddings": True}}
common = {"platform": "cpu", "devices": 8, "model_config": TINY, "resources": {"num_cpus": 0.1}, **tiny_model}
CAND = "benchmark/candidates.json"      # the serving cells are not in BENCHMARK.json yet
if which == "chat":
    ov = {**common, "traffic": {"users": 4, "rounds": 1500, "prompt_tokens": {"dist": "loguniform", "lo": 8, "hi": 40},
          "output_tokens": {"dist": "loguniform", "lo": 8, "hi": 24}, "first_round_output_tokens": 12, "lead_in_s": 1,
          "trace_seconds": 1,
          "engine": {"num_slots": 4, "max_len": 96, "prefill_buckets": [16, 32], "page_size": 16, "num_pages": 64, "chunk_steps": 2},
          "check": {"samples_per_tag": 2, "pad_to": 96, "max_regret": 1e-3}}}
    print(json.dumps(R.run_cell("serve-chat-closed", 2**31 + 11, 3, trace, ov, CAND))[:3000])
elif which == "docqa":
    ov = {**common, "traffic": {"users": 4, "groups": 640, "asks_per_doc": 4, "lag_groups": 3, "doc_grid": 8,
          "doc_tokens": {"dist": "uniform", "lo": 64, "hi": 128}, "question_tokens": {"dist": "uniform", "lo": 4, "hi": 12},
          "answer_tokens": {"dist": "uniform", "lo": 4, "hi": 10}, "first_round_output_tokens": 8, "lead_in_s": 1, "trace_seconds": 1,
          "engine": {"num_slots": 4, "max_len": 192, "prefill_buckets": [16, 32], "page_size": 16, "num_pages": 256, "chunk_steps": 2},
          "check": {"samples_per_tag": 1, "pad_to": 192, "max_regret": 1e-3}}}
    print(json.dumps(R.run_cell("serve-docqa-closed", 7, 3, trace, ov, CAND))[:3000])
else:
    four = which == "fsdp4"
    tm = {"config": {**tiny_model["config"], "tie_word_embeddings": False}}
    TT = {**TINY, "tie_embeddings": False, "attn_qkv_bias": False}
    ov = {"platform": "cpu", "devices": 4 if four else 1, "model_config": TT, **tm,
          "scaling": {"num_workers": 1, "use_tpu": False, "chips_per_worker": None},
          "jax_config": {"platform": "cpu", "cpu_devices_per_worker": 4 if four else 1},
          "traffic": {"batch": 8 if four else 2, "seq": 32, "host_batches": 8, "warmup_steps": 2, "trace_from_step": 1, "trace_steps": 2,
                      "check": {"loss_tolerance": 1e-3, "token_nll_rms_tolerance": 1e-3, "token_nll_max_tolerance": 1e-2}}}
    print(json.dumps(R.run_cell("train-fsdp4" if four else "train-1chip", 3, 2, trace, ov))[:3000])

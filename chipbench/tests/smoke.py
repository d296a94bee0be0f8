"""A cell at a size the CPU runs in seconds: h2o-danube-3-4b's layout at
the program's smoke widths, with short prompts and outputs."""
import copy

SMOKE_CONFIG = {
    "name": "h2o-danube-3-4b-smoke", "arch": "h2o-danube-3-4b",
    "reference": "dense",
    "num_hidden_layers": 2, "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 512, "sliding_window": 16, "rope_theta": 500000.0,
    "rms_norm_eps": 1e-05, "hidden_act": "silu",
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "chips": 1, "mesh": None, "batch": 4, "max_len": 64,
}

SMOKE_TRAFFIC = {
    "prompt_tokens": {"dist": "fixed", "value": 24},
    "output_tokens": {"dist": "lognormal", "median": 4, "sigma": 0.8,
                      "min": 2, "max": 10},
    "arrivals": {"process": "gamma", "cv": 2.0, "rate_per_s": 8.0},
    "schedule_seed": 1,
}

# set as the cells' limits are, from readings on the CPU at this size over
# 13 seeds: the program read at most 0.0279, the fp8 control at least 0.232
SMOKE_LIMITS = {"sample_requests": 8,
                "widest_logit_gap": {"limit": 0.08, "lower": 0.0279,
                                     "upper": 0.232}}


def smoke_cell(workload="h2o-4b.code-bursty", **config):
    from chipbench import harness
    cell = harness.resolve(workload)
    cfg = dict(copy.deepcopy(SMOKE_CONFIG), **config)
    return harness.Cell(workload, cfg["chips"], cfg,
                        copy.deepcopy(SMOKE_TRAFFIC),
                        copy.deepcopy(SMOKE_LIMITS), cell.metrics,
                        cell.end_to_end)

"""Tiny cells for the CPU tests: the two families at a few units of width,
float32, with traffic that finishes in a second or two."""
import copy

SSM = {"name": "tiny-ssm", "family": "ssm", "reference": "ssd",
       "n_layers": 2, "d_model": 64, "n_heads": 0, "n_kv_heads": 0,
       "d_ff": 0, "head_dim": 1, "vocab": 256, "rope": False,
       "norm_eps": 1e-5, "tie_embeddings": True,
       "ssm": {"d_state": 16, "d_conv": 4, "expand": 2, "head_dim": 16,
               "n_groups": 1, "chunk": 16},
       "dtype": "float32", "param_dtype": "float32"}

MOE = {"name": "tiny-moe", "family": "moe", "reference": "moe_transformer",
       "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
       "d_ff": 32, "head_dim": 16, "vocab": 256, "rope": True,
       "rope_theta": 10000.0, "norm_eps": 1e-6, "tie_embeddings": False,
       "moe": {"n_experts": 8, "top_k": 2, "d_expert": 32},
       "dtype": "float32", "param_dtype": "float32"}

CHAT = {"kind": "open_poisson", "rate_hz": 20.0, "schedule_seed": 7,
        "prompt": {"median": 12, "sigma": 0.8, "lo": 4, "hi": 40},
        "output": {"median": 6, "sigma": 0.8, "lo": 2, "hi": 16},
        "warmup_s": 0.5,
        "engine": {"max_batch": 4, "cache_len": 64, "prefill_chunk": 16,
                   "sync_every": 4},
        "check_requests": 3}

DOCS = {"kind": "closed_backlog", "concurrency": 6, "block": 16,
        "schedule_seed": 7,
        "prompt": {"median": 24, "sigma": 0.5, "lo": 8, "hi": 56},
        "output": {"median": 4, "sigma": 0.5, "lo": 2, "hi": 8},
        "warmup_s": 0.5,
        "engine": {"max_batch": 4, "cache_len": 64, "prefill_chunk": 16,
                   "sync_every": 4},
        "check_requests": 4}

# float32 program against the float32 reference: greedy choices agree
LIMITS = {"max_logit_gap": {"limit": 1e-3}, "min_tokens": 10}


def cell(config, traffic, name):
    from bench import cells
    spec = cells.load_spec()
    return cells.Cell(
        name=name, config=copy.deepcopy(config),
        traffic=copy.deepcopy(traffic),
        end_to_end=[m for m in spec["end_to_end"]
                    if cells._applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if cells._applies(m, name)],
        limits=copy.deepcopy(LIMITS))

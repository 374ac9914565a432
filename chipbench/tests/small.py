"""A cell at a size the CPU runs in seconds, for the tests."""
import copy

from chipbench.arch import dense

MODEL = {"family": "dense", "num_layers": 1, "d_model": 64, "num_heads": 4,
         "num_kv_heads": 4, "d_ff": 128, "vocab_size": 256,
         "norm": "layernorm", "act": "swiglu", "rope_theta": 10000.0,
         "tie_embeddings": False}
OPT = {"learning_rate": 0.001, "b1": 0.9, "b2": 0.999, "eps": 1e-08,
       "weight_decay": 0.0, "warmup_steps": 5, "total_steps": 100000,
       "grad_clip": 1.0, "z_loss": 0.0001}
TRAFFIC = {"generator": "regions", "regions": 2, "cameras_per_region": 2,
           "drifting_regions": [0, 1], "num_domains": 6, "domain_dim": 4,
           "tau": 0.15, "pool_rows": 256, "pool_len": 32,
           "draws_per_camera": 512, "lag_max": 2.0,
           "region_spread": 10.0, "region_distance": 1000.0,
           "sig_buckets": 64, "switch_time": 10.0, "region_stagger": 5.0}
SETTINGS = {
    "controller": {"window_micro": 2, "micro_steps": 4, "train_batch": 8,
                   "seq_len": 32, "sample_rate": 8, "drift_impl": "auto",
                   "shortlist_k": 2},
    "serve": {"num_slots": 4, "capacity": 64, "prompt_len": 8,
              "max_new": 8, "queries_per_stream": 4},
    "warmup_windows": 3,
    # CPU limits: every computation is float32 there except training,
    # which runs in bfloat16 as on the chip
    "limits": {"train_loss_gap": 0.02, "train_grad_gap": 0.1,
               "train_update_gap": 0.1, "eval_acc_gap": 0.02,
               "drift_score_gap": 1e-5, "serve_logit_gap": 0.05}}


def spec(serve=True):
    """A drifting cell: 2 regions x 2 cameras, both regions drift, two
    groups retrain and (with `serve`) answer queries."""
    settings = copy.deepcopy(SETTINGS)
    if not serve:
        settings.pop("serve")
        settings["limits"].pop("serve_logit_gap")
    return {"name": "small", "config": "small", "traffic": "small",
            "chips": 1, "settings": settings,
            "arch": dense,
            "model_file": {"name": "small", "arch": "dense",
                           "model": dict(MODEL), "optimizer": dict(OPT)},
            "traffic_params": dict(TRAFFIC),
            "end_to_end": [{"name": "window_s", "unit": "s"},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": []}

"""A tiny job of the benchmark's shape, small enough for the CPU."""

TINY = {
    "env": "traffic", "side": 2,
    "policy": {"kind": "fnn", "hidden": [16, 8], "gru_hidden": 8},
    "aip": {"kind": "fnn", "hidden": [8, 8], "gru_hidden": 8},
    "aip_train": {"lr": 1e-3, "epochs": 3, "batch": 128, "eval_chunk": 64},
    "ppo": {"lr": 2.5e-4, "gamma": 0.99, "lam": 0.95, "clip_eps": 0.1,
            "entropy_coef": 0.01, "value_coef": 1.0, "epochs": 2,
            "minibatches": 2, "max_grad_norm": 0.5},
    "dtype": "float32", "matmul_precision": "default",
    "aip_refresh": 3, "collect_streams": 3, "collect_steps": 8,
    "collect_holdout": 1, "ials_streams": 4, "rollout_steps": 4,
    "eval_episodes": 1, "async_collect": False, "max_aip_staleness": 2,
    "shards": 1, "sharded_gs": "auto",
}


def job(**kw):
    out = {k: (dict(v) if isinstance(v, dict) else v) for k, v in TINY.items()}
    out.update(kw)
    return out


def gru_job():
    return job(env="warehouse", side=2,
               policy={"kind": "gru", "hidden": [16, 8], "gru_hidden": 8},
               aip={"kind": "gru", "hidden": [8, 8], "gru_hidden": 8})

"""GSPMD-sharded page-pool serve drill (ISSUE 8) — run in a subprocess
because the 2-device CPU world (--xla_force_host_platform_device_count)
must be forced BEFORE jax initializes.

Serves one tiny mixed workload four ways in-process — {gather, ragged} ×
{unsharded, pool sharded P(None, None, "model", None) over 2 devices} —
and prints one JSON verdict line: sharded output must be token-identical
to unsharded for BOTH read paths, and the sharded pool must really live
on 2 devices.
"""
import json
import os
import sys

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2")
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import numpy as np  # noqa: E402
import jax  # noqa: E402

# pin the CPU platform before any array exists (backend choice is
# one-shot): a child of a test worker must never take a chip
jax.config.update("jax_platforms", "cpu")


def main():
    from paddle_tpu.inference import ContinuousBatcher
    from paddle_tpu.models.llama import LlamaConfig, llama_init_params

    assert len(jax.devices()) == 2, jax.devices()
    cfg = LlamaConfig.tiny(num_hidden_layers=2,
                           max_position_embeddings=128)  # KV heads = 2
    params = llama_init_params(cfg, jax.random.PRNGKey(3))
    rng = np.random.RandomState(5)
    reqs = [(rng.randint(1, cfg.vocab_size, n).tolist(), m)
            for n, m in [(5, 6), (13, 4)]]

    def serve(layout, shard):
        if shard:
            os.environ["PADDLE_SERVE_MESH_MODEL"] = "2"
        else:
            os.environ.pop("PADDLE_SERVE_MESH_MODEL", None)
        eng = ContinuousBatcher(cfg, params, max_batch=3, max_len=96,
                                prompt_buckets=(8, 16, 32), burst=4,
                                page_size=8, kv_layout=layout)
        rids = [eng.add_request(p, max_new_tokens=m) for p, m in reqs]
        res = eng.run()
        devs = len(eng._cache["k"][0].sharding.device_set)
        return [res[r] for r in rids], devs, eng._ragged

    gather_base, d1, _ = serve("paged", False)
    gather_shard, d2, _ = serve("paged", True)
    ragged_base, _, r_on = serve("ragged", False)
    ragged_shard, d3, rs_on = serve("ragged", True)

    print(json.dumps({
        "gather_parity": gather_shard == gather_base,
        "ragged_parity": ragged_shard == ragged_base,
        "cross_parity": ragged_base == gather_base,
        "pool_devices": [d1, d2, d3],
        "ragged_active": bool(r_on and rs_on),
    }))


if __name__ == "__main__":
    main()

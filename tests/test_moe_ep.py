"""Expert-parallel shard_map MoE vs the drop-free local layer on a real
multi-device mesh (subprocess: needs XLA_FLAGS device-count override before
jax init). The reduced config's capacity factor (4.0) is one at which the
EP buckets drop nothing, so both give the whole layer."""
import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np, dataclasses
    from jax.sharding import Mesh
    from repro.configs.registry import get_config
    from repro.distributed.sharding import ShardingEnv, use_sharding
    from repro.models import moe as moe_mod
    from repro.models.params import init_from_specs

    cfg = dataclasses.replace(get_config("qwen3-moe-30b-a3b-reduced"),
                              dtype="float32")
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
    env = ShardingEnv(mesh)
    env.ep_shard_map = True
    params = init_from_specs(moe_mod.moe_spec(cfg), jax.random.PRNGKey(0),
                             jnp.float32)
    B, S, d = 4, 20, cfg.d_model   # S=20 exercises the seq-padding path
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, d)) * 0.5
    y_ref, _, st_ref = moe_mod.moe_apply_local(params, x, cfg)
    with mesh, use_sharding(env):
        y_ep, _, st_ep = jax.jit(lambda p, x: moe_mod.moe_apply(p, x, cfg))(
            params, x)
    assert int(st_ref["moe_local_rows"]) == B * S * cfg.moe.top_k
    assert int(st_ep["moe_local_rows"]) == B * S * cfg.moe.top_k
    # the buffers the experts ran on hold every assignment
    assert int(st_ref["moe_buffer_rows"]) >= B * S * cfg.moe.top_k
    assert int(st_ep["moe_buffer_rows"]) >= B * S * cfg.moe.top_k
    err = float(jnp.abs(y_ref - y_ep).max() / (jnp.abs(y_ref).max() + 1e-9))
    assert err < 2e-3, f"EP mismatch: {err}"
    print("EP_OK", err)
""")


def test_ep_dispatch_matches_gspmd_on_8_devices():
    """The oracle is the drop-free local layer (``moe_apply_local``)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "EP_OK" in out.stdout, out.stdout + out.stderr

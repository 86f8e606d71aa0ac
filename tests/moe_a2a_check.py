"""All-to-all MoE correctness vs the dense dispatch path (8 fake devices).

Run by tests/test_distributed.py in a subprocess. With generous capacity
(nothing dropped), both dispatch implementations must produce identical
outputs up to fp tolerance.
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.launch.mesh import mesh_kwargs  # noqa: E402
from repro.models.moe import MoEConfig, moe_apply, moe_apply_a2a, moe_init  # noqa: E402


def main():
    assert len(jax.devices()) == 8
    mesh = jax.make_mesh((2, 4), ("data", "model"), **mesh_kwargs(2))
    d_model, d_ff = 32, 16
    cfg = MoEConfig(n_experts=8, top_k=2, capacity_factor=8.0)  # no drops
    params = moe_init(jax.random.PRNGKey(0), d_model, d_ff, cfg)
    t = 64
    x = jax.random.normal(jax.random.PRNGKey(1), (t, d_model), jnp.float32)

    y_ref, aux_ref = moe_apply(params, x, cfg)

    def fn(xl, router, w1, w3, w2):
        p = {"router": router, "w1": w1, "w3": w3, "w2": w2}
        y, aux = moe_apply_a2a(p, xl, cfg, ep=4, axis_name="model")
        return y, jax.lax.pmean(jax.lax.pmean(aux, "model"), "data")

    y_a2a, aux_a2a = jax.jit(jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P("data", None), P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(P("data", None), P()), check_vma=False,
    ))(x, params["router"], params["w1"], params["w3"], params["w2"])

    err = float(jnp.max(jnp.abs(y_ref - y_a2a)))
    print(f"a2a vs dense max abs err: {err:.3e}")
    if err > 1e-4:
        print("FAIL")
        sys.exit(1)

    # gradients flow through the a2a path
    def loss(w1):
        y, _ = jax.jit(jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P("data", None), P(None, None), P("model", None, None),
                      P("model", None, None), P("model", None, None)),
            out_specs=(P("data", None), P()), check_vma=False,
        ))(x, params["router"], w1, params["w3"], params["w2"])
        return jnp.sum(y * y)

    g = jax.grad(loss)(params["w1"])
    if not bool(jnp.isfinite(g).all()):
        print("FAIL: non-finite grads")
        sys.exit(1)
    print("grads finite OK")
    print("OK")


if __name__ == "__main__":
    main()

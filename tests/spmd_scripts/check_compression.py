"""Subprocess check: int8 compressed cross-pod gradient mean ~= exact mean,
and error feedback removes the bias over repeated rounds."""

import os

assert "--xla_force_host_platform_device_count=8" in os.environ.get("XLA_FLAGS", "")

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.training.compression import compressed_pmean, compressed_pmean_with_feedback

mesh = jax.sharding.Mesh(np.array(jax.devices()).reshape(2, 4), ("pod", "data"))

rng = np.random.default_rng(0)
g_global = rng.normal(size=(2, 4096)).astype(np.float32)  # per-pod gradients


def run(fn):
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P("pod", None),
                                 out_specs=P("pod", None), check_vma=False))(
        jnp.asarray(g_global))


exact = g_global.mean(axis=0)

got = np.asarray(run(lambda g: compressed_pmean(g[0], "pod")[None]))[0]
rel = np.abs(got - exact).mean() / (np.abs(exact).mean() + 1e-9)
assert rel < 0.02, rel
print(f"compressed_pmean rel err {rel:.4f} (<2%)")

# error feedback: accumulated mean over rounds converges to the true mean
res = jnp.zeros((4096,))
acc_c, acc_e = np.zeros(4096), np.zeros(4096)
for step in range(8):
    gs = rng.normal(size=(2, 4096)).astype(np.float32)

    def fb(g, r):
        m, nr = compressed_pmean_with_feedback(g[0], r[0], "pod")
        return m[None], nr[None]

    out, res = jax.jit(jax.shard_map(
        fb, mesh=mesh, in_specs=(P("pod", None), P("pod", None)),
        out_specs=(P("pod", None), P("pod", None)), check_vma=False))(
            jnp.asarray(gs), res[None].repeat(2, 0))
    res = res[0]
    acc_c += np.asarray(out)[0]
    acc_e += gs.mean(axis=0)
rel_fb = np.abs(acc_c - acc_e).mean() / (np.abs(acc_e).mean() + 1e-9)
assert rel_fb < 0.02, rel_fb
print(f"error-feedback cumulative rel err {rel_fb:.4f}")
print("COMPRESSION_OK")

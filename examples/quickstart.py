"""Quickstart: the paper's offload abstractions in five minutes.

Run:  PYTHONPATH=src:. python examples/quickstart.py
"""
import numpy as np
import jax
import jax.numpy as jnp

from repro.core import (
    HOST_OPT,
    OffloadRef,
    PrefetchSpec,
    memkind as mk,
    offload,
)

# ---------------------------------------------------------------------------
# 1. Paper Listing 1: decorate a kernel; arguments are passed BY REFERENCE
# ---------------------------------------------------------------------------
nums1 = np.random.randint(0, 100, 1000).astype(np.float32)
nums2 = np.random.randint(0, 100, 1000).astype(np.float32)


@offload
def mykernel(a, b):
    return a + b


print("listing-1 sum:", np.asarray(mykernel(nums1, nums2))[:5], "...")

# ---------------------------------------------------------------------------
# 2. Paper Listing 2: add a prefetch annotation — same result, streamed
#    through a bounded device buffer (buffer_size / elements_per_fetch /
#    distance are the paper's exact knobs)
# ---------------------------------------------------------------------------
spec = PrefetchSpec(buffer_size=10, elements_per_fetch=2, distance=4)


@offload(refs=dict(
    a=OffloadRef(kind=mk.PINNED_HOST, prefetch=spec),
    b=OffloadRef(kind=mk.PINNED_HOST, prefetch=spec),
))
def mykernel2(a, b):
    return a + b


big_a = np.random.randn(64, 1024).astype(np.float32)  # lives at the Host kind
big_b = np.random.randn(64, 1024).astype(np.float32)
out = mykernel2(big_a, big_b)
print("listing-2 streamed:", np.allclose(np.asarray(out), big_a + big_b))

# ---------------------------------------------------------------------------
# 3. Paper Listing 3 / §3.2: memory kinds — one line moves data between
#    hierarchy levels; the kind handles the mechanics
# ---------------------------------------------------------------------------
mesh = jax.make_mesh((1,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
x = jnp.arange(8.0)
x_host = mk.place(x, mesh, jax.sharding.PartitionSpec(), mk.PINNED_HOST)
x_dev = mk.place(x_host, mesh, jax.sharding.PartitionSpec(), mk.DEVICE)
print("kind round-trip:", np.allclose(np.asarray(x_dev), np.asarray(x)),
      f"(backend host-offload support: {mk.host_offload_supported()})")

# placement policies: the production form of the same idea
print("policy:", HOST_OPT.name, "-> optimizer state lives at",
      HOST_OPT.opt_state.jax_kind)

# ---------------------------------------------------------------------------
# 4. The TPU-native kernel level: weights stay in HBM, prefetched to VMEM
# ---------------------------------------------------------------------------
from repro.kernels.streamed_matmul import streamed_matmul, matmul_ref

xk = jax.random.normal(jax.random.PRNGKey(0), (256, 512), jnp.float32)
wk = jax.random.normal(jax.random.PRNGKey(1), (512, 256), jnp.float32)
y = streamed_matmul(xk, wk, spec=PrefetchSpec(buffer_size=3, elements_per_fetch=1, distance=2))
print("streamed matmul matches oracle:",
      np.allclose(np.asarray(y), np.asarray(matmul_ref(xk, wk)), atol=1e-3))

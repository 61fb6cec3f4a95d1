"""Pallas kernel check: rhizome_segment_reduce vs the jnp oracle.

The kernel runs the way ``kernels.ops`` chooses — compiled by Mosaic on a
TPU, interpreted elsewhere — and must agree with the oracle.  The row
reports the oracle's time and which way the kernel ran; it is no kernel
timing."""
import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timed
from repro.kernels import ops
from repro.kernels.ref import segment_combine_ref


def main():
    rng = np.random.default_rng(0)
    for e, nseg in ((4096, 1024), (16384, 4096)):
        data = jnp.asarray(rng.uniform(-1, 1, e).astype(np.float32))
        ids = jnp.asarray(np.sort(rng.integers(0, nseg, e)).astype(np.int32))
        for kind in ("min", "sum"):
            ref = jax.jit(lambda d, i: segment_combine_ref(d, i, nseg, kind))
            _ = ref(data, ids).block_until_ready()
            _, us_ref = timed(lambda: ref(data, ids).block_until_ready(),
                              repeats=5)
            out = ops.segment_combine(data, ids, nseg, kind)
            np.testing.assert_allclose(np.asarray(out),
                                       np.asarray(ref(data, ids)),
                                       rtol=5e-5, atol=1e-6)
            emit(f"kernel/{kind}/E{e}", us_ref,
                 f"oracle_us={us_ref:.0f};pallas=validated-"
                 + ("interpret" if ops._interpret() else "mosaic"))


if __name__ == "__main__":
    main()

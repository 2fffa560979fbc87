"""The groups == 1 conv unit hands each stage's GEMM its patch operand in
the layout the kernel reads: row-major (T, Kp), Kp = R·S·C rounded up to
the tile, so ``sparse_gemm`` pads nothing on that axis and no patch-sized
array is transposed or padded.  Traced with ``jax.make_jaxpr`` at VGG16
conv2's shape (224², 64 → 64, 3×3) and never run."""
import jax
import jax.numpy as jnp

from repro.core import policy as pol
from repro.core import sparse_conv
from repro.core.sparse_conv import relu_conv
from repro.kernels import stats

POLICY = pol.IN_OUT_WR.with_(kernel_impl="pallas")
N, H, W, C, M = 1, 224, 224, 64, 64
T, K, KP = N * H * W, 9 * C, 640


def _eqns(jaxpr):
    """Every equation of a jaxpr and its sub-jaxprs, Pallas kernel bodies
    left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def test_conv2_stage_gemms_get_tiled_patch_operands(monkeypatch):
    calls = []
    real = sparse_conv._mm

    def spy(a, b, *args, **kw):
        before = stats.counts()
        out = real(a, b, *args, **kw)
        padded = {k.split(":")[1] for k, v in stats.counts().items()
                  if k.startswith("pad_operand:") and v > before.get(k, 0)}
        calls.append((a.shape, b.shape, padded))
        return out

    monkeypatch.setattr(sparse_conv, "_mm", spy)
    x = jax.ShapeDtypeStruct((N, H, W, C), jnp.float32)
    w = jax.ShapeDtypeStruct((3, 3, C, M), jnp.float32)
    jx = jax.make_jaxpr(jax.grad(
        lambda x, w: relu_conv(x, w, 1, "SAME", POLICY).sum(), (0, 1)))(x, w)

    fp, bp, wg = calls
    assert fp[0] == bp[0] == (T, KP)           # patches @ weights
    assert wg[:2] == ((M, T), (T, KP))         # dWᵀ = dyᵀ · P
    # Only the 64-wide side (weights, σ′ multiplier, dyᵀ) is padded to a
    # whole tile, never the patch operand.
    assert fp[2] == {"b"} and bp[2] == {"b", "mult"} and wg[2] == {"a"}
    big = [(e.primitive.name, v.aval.shape) for e in _eqns(jx.jaxpr)
           if e.primitive.name in ("transpose", "pad")
           for v in e.outvars if v.aval.size >= T * K]
    assert big == []

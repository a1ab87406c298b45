"""Paper Listing 1 — the xmnmc programming model, end to end.

A 3-channel convolutional layer executed as THREE matrix reservations and ONE
complex instruction, exactly like the paper's C listing:

    // Reservation
    _xmr_w(m0, A, 1, rowsA, colsA);
    _xmr_w(m1, F, 1, rowsF, colsF);
    _xmr_w(m2, R, 1, rowsR, colsR);
    // Matrix Kernel
    _conv_layer_w(m2, m0, m1);

The program is built through the shared kernel IR (``repro.core.program``) —
``issue_program`` emits precisely those four instructions — and runs the full
ARCANE simulator stack (CV-X-IF bridge → software decode → hazard check →
VPU dispatch → 2D-DMA allocation → fused compute → deferred write-back),
prints the phase split (Fig. 3) and the modeled speedup vs a scalar-CPU
execution (Fig. 4), then cross-checks the same fused instruction against its
TPU-target Pallas kernel (interpret mode) and the jnp oracle.
"""
import numpy as np

from repro.core import (ArcaneCoprocessor, ElemWidth, ProgramBuilder,
                        ProgramRun, issue_program, place_program)
from repro.core.isa import _convlayer_preamble


def build_listing1(h: int = 64, w: int = 64, k: int = 3):
    """Listing 1 as a KernelProgram: one fused conv-layer instruction over
    the whole image (it fits the register file at 64x64; larger inputs go
    through ``repro.lower.lower_cnn``, which strip-mines the same op)."""
    b = ProgramBuilder("listing1", ElemWidth.W)
    b.buffer("A", 3 * h, w, init="random", seed=0, lo=-8, hi=8)
    b.buffer("F", 3 * k, k, init="random", seed=1, lo=-4, hi=4)
    b.buffer("R", (h - k + 1) // 2, (w - k + 1) // 2)
    # _xmr_w(m0, A, ...); _xmr_w(m1, F, ...); _xmr_w(m3, R, ...)  (issued by
    # issue_program as the op's reservations)
    b.op("conv_layer", [b.full("A"), b.full("F")], b.full("R"),
         comment="_conv_layer_w(m3, m0, m1)   // Listing 1 Matrix Kernel")
    return b.build()


def main():
    H = W = 64
    K = 3
    prog = build_listing1(H, W, K)
    A = prog.buffer("A").materialize(prog.width)
    F = prog.buffer("F").materialize(prog.width)

    cop = ArcaneCoprocessor(n_vpus=4, vregs_per_vpu=64, vlen_bytes=1024,
                            lanes=8)
    addrs = place_program(cop, prog)      # host stores (coherent), untimed
    cop.rt.stats.reset()
    issue_program(cop, prog, addrs)       # ---- Listing 1: 3x xmr + 1x xmk4
    run = ProgramRun(prog=prog, cop=cop, addrs=addrs)
    R = run.gather("R")                   # RAW-checked host load

    # oracle
    from repro.kernels.convlayer.ref import conv_layer_ref
    import jax.numpy as jnp
    x = jnp.asarray(A.reshape(3, H, W))
    f = jnp.asarray(F.reshape(1, 3, K, K))
    ref = np.asarray(conv_layer_ref(x, f))[0]
    assert np.array_equal(R, ref), "simulator disagrees with jnp oracle"

    # TPU-target Pallas kernel (interpret mode on CPU)
    from repro.kernels import conv_layer
    pk = np.asarray(conv_layer(x, f, block_rows=16))[0]
    assert np.array_equal(pk, ref), "pallas kernel disagrees with oracle"

    stats = cop.rt.stats
    print(f"conv layer {H}x{W} 3ch int32 on 8-lane ARCANE")
    print(f"  result {R.shape}, checksum {int(R.astype(np.int64).sum())}")
    print(f"  kernels run: {stats.kernels_run}, cycles: {stats.total_cycles}")
    shares = stats.shares()
    print("  phase split: " + "  ".join(
        f"{k}={v:.1%}" for k, v in shares.items()))
    # CV32E40X-class scalar baseline: ~3 cycles/MAC inner loop + ld/op/st per
    # elementwise op (the same model benchmarks/fig4_speedup.py sweeps)
    _, cost = _convlayer_preamble([(3 * H, W), (3 * K, K)], {}, ElemWidth.W)
    scalar = 3 * cost.macs + 3 * cost.elementwise
    print(f"  modeled speedup vs scalar RV32IMC: "
          f"{scalar / stats.total_cycles:.1f}x")
    print("  simulator == pallas kernel == jnp oracle ✓")


if __name__ == "__main__":
    main()

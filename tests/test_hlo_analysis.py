"""HLO static cost model: trip-count awareness, dot flops, collectives."""
import jax
import jax.numpy as jnp

from repro.launch import hlo_analysis as H


def _compile(f, *sds):
    return jax.jit(f).lower(*sds).compile().as_text()


def test_scan_vs_unroll_flops_parity():
    A = jnp.zeros((256, 256))

    def scanned(x):
        def body(c, _):
            return c @ A, None
        y, _ = jax.lax.scan(body, x, None, length=12)
        return y

    def unrolled(x):
        for _ in range(12):
            x = x @ A
        return x

    sds = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    fs = H.analyze(_compile(scanned, sds)).flops
    fu = H.analyze(_compile(unrolled, sds)).flops
    expect = 12 * 2 * 256**3
    assert abs(fs - expect) / expect < 0.01
    assert abs(fu - expect) / expect < 0.01


def test_nested_scan_trip_counts_compose():
    A = jnp.zeros((128, 128))

    def nested(x):
        def outer(c, _):
            def inner(ci, _):
                return ci @ A, None
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None
        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y

    sds = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    f = H.analyze(_compile(nested, sds)).flops
    expect = 15 * 2 * 128**3
    assert abs(f - expect) / expect < 0.02


def test_dot_flops_batched():
    def f(a, b):
        return jnp.einsum("bij,bjk->bik", a, b)
    sds_a = jax.ShapeDtypeStruct((4, 64, 32), jnp.float32)
    sds_b = jax.ShapeDtypeStruct((4, 32, 16), jnp.float32)
    flops = H.analyze(_compile(f, sds_a, sds_b)).flops
    expect = 2 * 4 * 64 * 32 * 16
    assert abs(flops - expect) / expect < 0.01


def test_shape_bytes():
    assert H._shape_bytes("f32[16,4]") == 256
    assert H._shape_bytes("bf16[8]") == 16
    assert H._shape_bytes("(f32[4], s32[2])") == 24
    assert H._shape_bytes("pred[10]") == 10


# trimmed from a program compiled for a v5e: TPU layouts carry tiling and
# memory-space annotations in parens (``{0:T(128)S(1)}``)
_TPU_HLO = """\
ENTRY %main.1 (p0: bf16[8,512], p1: f32[8]) -> (bf16[8,2048], f32[8]) {
  %p0 = bf16[8,512]{1,0:T(8,128)(2,1)} parameter(0)
  %p1 = f32[8]{0:T(128)} parameter(1)
  %all-reduce.20 = f32[8]{0:T(128)S(1)} all-reduce(%p1), channel_id=5, replica_groups=[1,4]<=[4], to_apply=%add
  %all-gather.21 = bf16[8,2048]{1,0:T(8,128)(2,1)S(1)} all-gather(%p0), channel_id=13, replica_groups=[1,4]<=[4], dimensions={1}
  ROOT %tuple.1 = (bf16[8,2048]{1,0:T(8,128)(2,1)}, f32[8]{0:T(128)}) tuple(%all-gather.21, %all-reduce.20)
}
"""


def test_tpu_layouts_parse():
    cost = H.analyze(_TPU_HLO)
    assert cost.count_by_type["all-gather"] == 1
    assert cost.count_by_type["all-reduce"] == 1
    assert H.instruction_shapes(_TPU_HLO, "all-gather") == [(8, 2048)]


def _gather_hlo(gather_shape: str, offset_dims: str, user_shape: str) -> str:
    return f"""\
ENTRY %main.1 (p0: f32[8,64], p1: s32[192,1]) -> f32[1] {{
  %p0 = f32[8,64]{{1,0}} parameter(0)
  %p1 = s32[192,1]{{1,0}} parameter(1)
  %gather.5 = f32[{gather_shape}]{{2,1,0}} gather(%p0, %p1), offset_dims={{{offset_dims}}}, collapsed_slice_dims={{}}, start_index_map={{1}}, index_vector_dim=1, slice_sizes={{8,1}}
  ROOT %bitcast.6 = f32[{user_shape}]{{3,2,1,0}} bitcast(%gather.5)
}}
"""


def test_gather_shape_restored_only_through_batch_dim_splits():
    # XLA's form for a vmapped take: batch dim 192 = 32 x 6 flattened,
    # offset dims (8, 1); the bitcast splits the batch dim back
    hlo = _gather_hlo("192,8,1", "1,2", "1,32,6,8")
    assert H.instruction_shapes(hlo, "gather") == [(1, 32, 6, 8)]
    # a bitcast that moves the offset dim is a transpose, not a restore
    hlo = _gather_hlo("192,8,1", "1,2", "8,32,6,1")
    assert H.instruction_shapes(hlo, "gather") == [(192, 8, 1)]
    # nor one that splits an offset dim
    hlo = _gather_hlo("192,8,1", "1,2", "192,2,4")
    assert H.instruction_shapes(hlo, "gather") == [(192, 8, 1)]


def test_roofline_terms_and_dominance():
    t = H.roofline_terms(197e12, 819e9, 0.0, 1)
    assert t["compute_s"] == 1.0 and t["memory_s"] == 1.0
    assert H.dominant_term({"compute_s": 2, "memory_s": 1, "collective_s": 0}) == "compute_s"

"""The compiler's verdict on the main path's kernels, without a chip.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (on-chip-measurement guide, section 2). Every case
compiles one kernel at the real shapes of ``chip_smoke.py`` — Llama-2-7B
widths: 32 heads x 128, bf16 — for one described v5e chip, in this
process. A compile that passes is not a chip run; it guards every later
PR against what interpret mode cannot see (tiling, alignment, VMEM).

This is the ONE file that describes a topology: only one process may
hold the TPU library, a second file could land on another xdist worker,
and its fixture would then skip in silence. ``get_topology_desc`` runs
inside the ``topo`` fixture, never at import.
"""
import chip_smoke as cs
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from paddle_tpu.models import LlamaConfig
from paddle_tpu.ops import flash_attention as fa
from paddle_tpu.ops import ragged_attention as ra

# chip_smoke.py's shapes, read from it so that they cannot drift
_CFG = LlamaConfig.llama2_7b()
HEADS, HEAD_DIM = _CFG.num_attention_heads, _CFG.head_dim
TRAIN_QKV = (cs.TRAIN_BATCH, cs.TRAIN_SEQ, HEADS, HEAD_DIM)   # [B, T, H, D]
PREFILL_BUCKETS = cs.SERVE_PROMPT_BUCKETS
SERVE_BATCH, SERVE_MAX_LEN = cs.SERVE_MAX_BATCH, cs.SERVE_MAX_LEN

# what Mosaic says to the ragged kernel (jax 0.9.0, libtpu 0.0.34)
REFUSAL_STORE = ("cannot statically prove that index in dimension 1 is a "
                 "multiple of 128")
REFUSAL_SLICE = ("Slice shape along dimension 2 must be aligned to tiling "
                 "(8), but is 1")
# (q rows, kv heads, page_size, the compiler's message); page_size 16 is
# the batcher's default, 128 is where the first refusal gives way
RAGGED_CASES = {
    "decode": (1, HEADS, 16, REFUSAL_STORE),
    "decode_gqa": (1, 8, 16, REFUSAL_STORE),
    "prefill": (PREFILL_BUCKETS[0], HEADS, 16, REFUSAL_STORE),
    "decode_page128": (1, HEADS, 128, REFUSAL_SLICE),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _qkv(shape, sharding, dtype=jnp.bfloat16):
    return (jax.ShapeDtypeStruct(shape, dtype, sharding=sharding),) * 3


def _kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _flash(q, k, v):
    """What flash_attention_raw runs on a TPU at these shapes (its platform
    gate sees this process's CPU, so the kernel path is called directly)."""
    L, S = q.shape[1], k.shape[1]
    return fa._flash_kernel(q, k, v, causal=True, bq=fa._fit_block(512, L),
                            bk=fa._fit_block(512, S))


def _flash_loss(q, k, v):
    return _flash(q, k, v).astype(jnp.float32).sum()


class TestFlashCompiles:
    def test_forward_at_train_shape(self, one_chip, no_compile_cache):
        c = jax.jit(_flash).lower(*_qkv(TRAIN_QKV, one_chip)).compile()
        assert _kernels(c) == 1

    def test_grad_at_train_shape(self, one_chip, no_compile_cache):
        g = jax.grad(_flash_loss, argnums=(0, 1, 2))
        c = jax.jit(g).lower(*_qkv(TRAIN_QKV, one_chip)).compile()
        assert _kernels(c) == 3             # forward, dq, dk+dv

    def test_head_dim_64_pad_branch(self, one_chip, no_compile_cache):
        g = jax.value_and_grad(_flash_loss, argnums=(0, 1, 2))
        c = jax.jit(g).lower(*_qkv((2, 2048, 12, 64), one_chip)).compile()
        assert _kernels(c) == 3

    @pytest.mark.parametrize("bucket", PREFILL_BUCKETS)
    def test_forward_at_prefill_bucket(self, one_chip, no_compile_cache,
                                       bucket):
        c = jax.jit(_flash).lower(
            *_qkv((1, bucket, HEADS, HEAD_DIM), one_chip)).compile()
        assert _kernels(c) == 1

    def test_sharded_step_runs_kernel_per_shard(self, topo, no_compile_cache,
                                                monkeypatch):
        """The mesh train step: Mosaic kernels cannot be partitioned by
        GSPMD, so flash_attention_raw wraps the kernel in a shard_map over
        (batch on dp, heads on tp). The platform gate is steered here, in
        the test — it asks jax.default_backend(), which is this CPU."""
        monkeypatch.setattr(fa, "flash_attention_tpu_available", lambda: True)
        mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("dp", "tp"))
        spec = P("dp", None, "tp", None)

        def loss(q, k, v):
            return fa.flash_attention_raw(
                q, k, v, causal=True, mesh=mesh,
                spec=spec).astype(jnp.float32).sum()

        c = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            *_qkv(TRAIN_QKV, NamedSharding(mesh, spec))).compile()
        assert _kernels(c) == 3


def _ragged_lowered(q_rows, kv_heads, page_size, sharding):
    max_pages = SERVE_MAX_LEN // page_size
    pool_pages = SERVE_BATCH * max_pages + 1

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    pool = sds((pool_pages, page_size, kv_heads, HEAD_DIM), jnp.bfloat16)
    lens = sds((SERVE_BATCH,), jnp.int32)
    return ra.ragged_paged_attention.lower(
        sds((SERVE_BATCH, q_rows, HEADS, HEAD_DIM), jnp.bfloat16), pool, pool,
        sds((SERVE_BATCH, max_pages), jnp.int32), lens, lens,
        page_size=page_size, interpret=False)


class TestRaggedKernelVerdict:
    """Strict xfails: the day the kernel compiles, the suite says so — and
    ``supported()`` and ``ContinuousBatcher(kv_layout='ragged')`` then
    change with it."""

    @pytest.mark.parametrize("case", [
        pytest.param(name, marks=pytest.mark.xfail(
            strict=True, reason=f"Mosaic refuses the ragged kernel: {msg}"))
        for name, (_, _, _, msg) in RAGGED_CASES.items()])
    def test_compiles_at_serve_geometry(self, one_chip, no_compile_cache,
                                        case):
        q_rows, kv_heads, page_size, _ = RAGGED_CASES[case]
        _ragged_lowered(q_rows, kv_heads, page_size, one_chip).compile()

    @pytest.mark.parametrize("case", sorted(RAGGED_CASES))
    def test_refusal_is_the_recorded_one(self, one_chip, no_compile_cache,
                                         case):
        """The reason on the xfail above is the compiler's own message —
        and supported() says what the compiler says."""
        q_rows, kv_heads, page_size, msg = RAGGED_CASES[case]
        with pytest.raises(Exception) as err:
            _ragged_lowered(q_rows, kv_heads, page_size, one_chip).compile()
        assert msg in str(err.value)
        assert not ra.supported(HEAD_DIM, page_size, interpret=False)
        assert msg in " ".join(ra.supported.__doc__.split())

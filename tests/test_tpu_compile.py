"""The compiler's verdict on the main path's kernels, without a chip.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (on-chip-measurement guide, section 2). The kernel
cases compile one kernel at the real shapes of ``chip_smoke.py`` —
Llama-2-7B widths: 32 heads x 128, bf16 — or of a benchmark cell, the
program cases the two paged programs of each serving cell at one layer
with the kernels inside them, for one described v5e chip, in this
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
# the shapes the benchmark's cells hand the flash kernel (ISSUE 31), from the
# microbenchmark that times them so that they cannot drift
from tools.flash_microbench import GEOMETRIES as CELL_GEOMETRIES  # noqa: E402
SERVE_BATCH, SERVE_MAX_LEN = cs.SERVE_MAX_BATCH, cs.SERVE_MAX_LEN

# the decode read (ISSUE 28; the slots in order on an "arbitrary" grid with
# an SMEM carry since ISSUE 33) at the two serving cells' geometries, from
# the microbenchmark that times them so that they cannot drift (the batch
# cell: perfbench/traffic/longctx-batch.json, 48 slots, a 128-page table,
# pages of 16; InternLM2-1.8B: 8 KV heads x 128, bf16; the hybrid cell: 24
# slots, a 216-page table, 30 KV heads padded to 32), at chip_smoke's, and
# one case past each rule of ra.decode_supported()
from tools.paged_decode_microbench import GEOMETRIES as _READ  # noqa: E402
_CELL, _HYBRID_CELL = (
    {**{k: v for k, v in _READ[name].items() if k != "contexts"},
     "dtype": "bfloat16"} for name in ("batch", "hybrid"))
DECODE_CASES = {
    "batch_cell": (_CELL, None),
    "hybrid_cell": (_HYBRID_CELL, None),
    "chip_smoke": ({**_CELL, "slots": SERVE_BATCH,
                    "table_pages": SERVE_MAX_LEN // 16, "kv_heads": HEADS,
                    "q_heads": HEADS}, None),
    "chip_smoke_f32": ({**_CELL, "slots": SERVE_BATCH,
                        "table_pages": SERVE_MAX_LEN // 16,
                        "kv_heads": HEADS, "q_heads": HEADS,
                        "dtype": "float32"}, None),
    "table_of_2048_pages": ({**_CELL, "slots": 8, "table_pages": 2048},
                            None),
    "kv_heads_12": ({**_CELL, "slots": 8, "kv_heads": 12, "q_heads": 24},
                    None),
    "head_dim_64": ({**_CELL, "slots": 8, "head_dim": 64},
                    "Slice shape along dimension 2 must be aligned to "
                    "tiling (128), but is 64"),
    "page_of_4_rows": ({**_CELL, "slots": 8, "page_size": 1, "kv_heads": 4,
                        "q_heads": 8},
                       "Slice shape along dimension 1 must be aligned to "
                       "tiling (8), but is 4"),
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

    @pytest.mark.parametrize("name,shape,grad", CELL_GEOMETRIES,
                             ids=[g[0] for g in CELL_GEOMETRIES])
    def test_at_the_cells_geometries(self, one_chip, no_compile_cache, name,
                                     shape, grad):
        """The five shapes the benchmark's cells run (ISSUE 31): the train
        cell's forward and gradient, the serving cells' prefill buckets
        forward only, each with K and V held whole in VMEM."""
        assert fa._major_block(fa._fit_block(512, shape[1]), shape[1],
                               2 * shape[3]) == shape[1]
        c = jax.jit(_flash).lower(*_qkv(shape, one_chip)).compile()
        assert _kernels(c) == 1
        if grad:
            g = jax.grad(_flash_loss, argnums=(0, 1, 2))
            c = jax.jit(g).lower(*_qkv(shape, one_chip)).compile()
            assert _kernels(c) == 3

    @pytest.mark.parametrize("rows,dtype,major", [
        (8192, jnp.bfloat16, 8192),     # the longest sequence held whole
        (16384, jnp.bfloat16, 8192),    # past it: two major blocks a head,
        (8192, jnp.float32, 4096),      # and float32 rows cost twice
    ])
    def test_on_each_side_of_the_major_block_rule(self, one_chip,
                                                  no_compile_cache, rows,
                                                  dtype, major):
        """K and V (q and dout in the dk/dv kernel) are held in VMEM up to
        8 MiB, double-buffered; a longer sequence brings the second grid
        axis and its clamped index maps in."""
        assert fa._major_block(512, rows,
                               128 * jnp.dtype(dtype).itemsize) == major
        g = jax.grad(_flash_loss, argnums=(0, 1, 2))
        c = jax.jit(g).lower(*_qkv((1, rows, 2, 128), one_chip,
                                   dtype)).compile()
        assert _kernels(c) == 3

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


def _shapes(sharding, dtype="bfloat16"):
    """shape -> ShapeDtypeStruct on `sharding`, `dtype` unless one is given."""
    return lambda shape, dt=dtype: jax.ShapeDtypeStruct(
        shape, jnp.dtype(dt), sharding=sharding)


def _decode_lowered(sharding, slots, table_pages, page_size, kv_heads,
                    q_heads, head_dim, dtype):
    sds = _shapes(sharding, dtype)
    pool = sds((slots * table_pages + 1, page_size, kv_heads, head_dim))
    lens = sds((slots,), "int32")
    return ra.paged_decode_attention.lower(
        sds((slots, 1, q_heads, head_dim)), pool, pool,
        sds((slots, table_pages), "int32"), lens, lens, interpret=False)


@pytest.mark.parametrize("case", sorted(DECODE_CASES))
def test_decode_read_verdict(one_chip, no_compile_cache, case):
    """The compiler's verdict on the decode read, and decode_supported()
    saying the same; its body holds two chunks whatever the table's
    width, so no case knows a max_len."""
    geometry, refusal = DECODE_CASES[case]
    lowered = _decode_lowered(one_chip, **geometry)
    says = ra.decode_supported(geometry["head_dim"], geometry["kv_heads"],
                               geometry["page_size"])
    if refusal is None:
        assert _kernels(lowered.compile()) == 1 and says
        return
    with pytest.raises(Exception) as err:
        lowered.compile()
    assert refusal in str(err.value) and not says
    assert refusal in " ".join(ra.decode_supported.__doc__.split())


def test_decode_read_at_the_cell_is_one_named_kernel(one_chip,
                                                     no_compile_cache):
    """paged_decode_attention at the cell's geometry: the kernel's name in
    the compiled program, and the pool's [pages, rows, heads, hd] ->
    [pages, rows * heads, hd] view a bitcast, not a copy of the pool."""
    g = _CELL
    sds = _shapes(one_chip)
    pool = sds((4096, g["page_size"], g["kv_heads"], g["head_dim"]))
    lens = sds((g["slots"],), "int32")
    text = ra.paged_decode_attention.lower(
        sds((g["slots"], 1, g["q_heads"], g["head_dim"])), pool, pool,
        sds((g["slots"], g["table_pages"]), "int32"), lens, lens,
        interpret=False).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1 and "paged_decode_attention" in calls[0]
    assert not [ln for ln in text.splitlines()
                if " copy(" in ln and "bf16[4096," in ln]


@pytest.mark.parametrize("case,rows,kv_heads,compiles", [
    ("decode_row_at_the_cell", 1, 8, True),
    ("prefill_pages_at_the_cell", 16, 8, True),
    ("decode_row_chip_smoke", 1, HEADS, True),
    ("decode_row_of_4_heads", 1, 4, False)])
def test_kv_scatter_verdict(one_chip, no_compile_cache, case, rows, kv_heads,
                            compiles):
    """paged_kv_scatter (the one-launch K/V row write) for a described
    v5e, scatter_supported() saying the same, and the pools written in
    place: no copy of a pool in the compiled program."""
    n, ps, hd = 48, 16, 128
    sds = _shapes(one_chip)
    pool, src = sds((4096, ps, kv_heads, hd)), sds((n, rows, kv_heads, hd))
    lowered = jax.jit(
        lambda *a: ra.paged_kv_scatter(*a, interpret=False),
        donate_argnums=(0, 1)).lower(pool, pool, src, src,
                                     sds((n,), "int32"), sds((n,), "int32"))
    assert ra.scatter_supported(hd, kv_heads, ps) is compiles
    if not compiles:
        with pytest.raises(Exception, match="aligned to tiling"):
            lowered.compile()
        return
    text = lowered.compile().as_text()
    assert _kernels(lowered.compile()) == 1
    assert not [ln for ln in text.splitlines()
                if " copy(" in ln and "bf16[4096," in ln]


# --------------------------------------------- a model of 30 KV heads (PR 30)

def _pool_copies(text, pages) -> list:
    return [ln for ln in text.splitlines()
            if " copy(" in ln and f"bf16[{pages}," in ln]


def test_pool_of_30_kv_heads_is_padded_to_whole_tiles(one_chip,
                                                      no_compile_cache):
    """Olmo-Hybrid's full layers have 30 KV heads x 128. The decode kernel
    compiles for such a pool, but its [pages, rows * heads, hd] view of the
    tiled 4-D pool is then a COPY of the whole pool, K and V, every call;
    with the rows padded to 32 heads (llama_paged.pool_kv_heads) it is a
    bitcast again, and paged_kv_scatter takes the pool."""
    from paddle_tpu.models.llama_paged import pool_kv_heads
    cfg = LlamaConfig(hidden_size=3840, num_attention_heads=30,
                      num_key_value_heads=30, head_dim=128)
    assert pool_kv_heads(cfg) == 32 and pool_kv_heads(cfg, "int8") == 30
    assert pool_kv_heads(cfg, None, mesh=object()) == 30    # a sharded pool
    assert pool_kv_heads(_CFG) == HEADS                     # 32: as it is
    assert pool_kv_heads(LlamaConfig.tiny()) == 2           # the tiny ones
    sds = _shapes(one_chip)
    lens = sds((24,), "int32")

    def text(heads):
        pool = sds((1024, 16, heads, 128))
        return ra.paged_decode_attention.lower(
            sds((24, 1, heads, 128)), pool, pool, sds((24, 216), "int32"),
            lens, lens, interpret=False).compile().as_text()

    assert _pool_copies(text(30), 1024)                     # why it is padded
    assert not _pool_copies(text(32), 1024)
    assert ra.scatter_supported(128, 32, 16) \
        and not ra.scatter_supported(128, 30, 16)


def test_gated_delta_rule_compiles_at_the_cells_shapes(one_chip,
                                                       no_compile_cache):
    """ops/gated_delta.py at Olmo-Hybrid's widths (30 heads, key 96, value
    192) for a described v5e: one token of 24 slots, and the chunk scan
    over the longer prompt bucket of perfbench/traffic/longdoc-batch.json."""
    from paddle_tpu.ops.gated_delta import gdn_chunk_scan, gdn_step
    sds = _shapes(one_chip)
    B, T, H, dk, dv = 24, 3072, 30, 96, 192
    step = jax.jit(gdn_step).lower(
        sds((B, H, dk)), sds((B, H, dk)), sds((B, H, dv)),
        sds((B, H), "float32"), sds((B, H), "float32"),
        sds((B, H, dv, dk), "float32")).compile()
    assert step.memory_analysis().temp_size_in_bytes < 2 * B * H * dv * 128 * 4
    scan = jax.jit(gdn_chunk_scan).lower(
        sds((T, H, dk)), sds((T, H, dk)), sds((T, H, dv)),
        sds((T, H), "float32"), sds((T, H), "float32"),
        sds((H, dv, dk), "float32"), sds((), "int32")).compile()
    assert scan.memory_analysis().temp_size_in_bytes < 2 ** 30


# ------------------- the serving cells' two programs, the kernels inside them

def _cell_model(workload):
    """(one layer of the cell's model as the program's LlamaConfig, the
    cell's engine settings): the widths from the benchmark's own files
    through its own family modules, so that they cannot drift. The hybrid's
    one layer is a FULL layer: 30 KV heads, a pool padded to 32."""
    import dataclasses
    from tools.burst_weight_copies import cell_model
    model, settings = cell_model(workload)
    kinds = None if model.layer_types is None else (model.FULL,)
    return dataclasses.replace(model, num_hidden_layers=1,
                               layer_types=kinds), settings


@pytest.mark.parametrize("program", ["decode_burst", "prefill_slot"])
@pytest.mark.parametrize("workload", ["internlm2-1.8b.longctx-batch",
                                      "olmo-hybrid-7b.longdoc-batch"])
def test_paged_program_of_a_cell_holds_its_kernels(one_chip,
                                                   no_compile_cache,
                                                   monkeypatch, workload,
                                                   program):
    """What both serving cells run, compiled whole for a described v5e at
    one layer of the cell's widths, slots, page bucket, pool and longest
    prompt bucket: ``llama_paged_decode_burst`` holds the decode read and
    the one-launch row write, ``llama_paged_prefill_slot`` the flash
    forward and the page write. Kernels that compile alone have been
    refused inside a program (a pool view that became a copy, PR 30). The
    flash kernel's platform gate asks jax.default_backend(), which is this
    CPU: steered here, in the test."""
    monkeypatch.setattr(fa, "flash_attention_tpu_available", lambda: True)
    from paddle_tpu.inference.paging import pages_for_budget
    from paddle_tpu.models import llama_init_params
    from paddle_tpu.models.llama_paged import (
        init_paged_kv_cache, llama_paged_decode_burst,
        llama_paged_prefill_slot, page_bytes, paged_kv_read)
    cfg, eng = _cell_model(workload)
    ps, B = eng["page_size"], eng["max_batch"]
    assert paged_kv_read(cfg, ps) == "kernel"
    # the cell's pool: the pages its byte budget buys one layer of
    pages = pages_for_budget(eng["pool_hbm_bytes"], page_bytes(cfg, ps))
    pages = min(pages, B * eng["max_len"] // ps + 1)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda key: llama_init_params(cfg, key), jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(
        lambda: init_paged_kv_cache(cfg, pages, ps, max_batch=B)))
    sds = _shapes(one_chip, "int32")
    key = on_chip(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    if program == "decode_burst":
        text = llama_paged_decode_burst.lower(
            params, cache, sds((B, eng["page_buckets"][-1])), sds((B,)),
            sds((B,)), sds((B,), "bool"), sds((B,)), sds(()), key,
            config=cfg, n=eng["burst"], kv_read="kernel",
            interpret=False).compile().as_text()
        want = ("paged_decode_attention", "paged_kv_scatter")
    else:
        bucket = eng["prompt_buckets"][-1]
        text = llama_paged_prefill_slot.lower(
            params, cache, sds((bucket,)), sds((bucket // ps,)), sds(()),
            key, config=cfg, kv_read="kernel",
            interpret=False).compile().as_text()
        want = ("flash_fwd", "paged_kv_scatter")
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    for name in want:
        assert [ln for ln in calls if name in ln], (name, len(calls))
    assert not _pool_copies(text, pages)


@pytest.mark.parametrize("program", ["decode_burst", "prefill_slot"])
def test_paged_program_of_the_expert_cell_holds_its_kernels(
        one_chip, no_compile_cache, monkeypatch, program):
    """`k-exaone-236b.reasoning-batch` at three layers of its widths (a
    window layer with the dense FFN, a window layer and a full layer with
    experts), its 64 slots, page bucket, pool and longest prompt bucket,
    compiled whole for a described v5e under paddle_tpu's jax_enable_x64:
    the burst reads pool AND ring through `paged_decode_attention`, writes
    both through `paged_kv_scatter`, and runs the experts' three grouped
    products a sparse layer (`gmm`, whose grid was an i64 the compiler
    refused until it was traced 32-bit); the prefill holds the flash
    forward, with a window in two layers of three. No stacked expert weight
    is copied: the products read a layer's experts in place (a slice as the
    kernel's operand was 384 MiB a matrix a layer a step, PR 34)."""
    import dataclasses
    monkeypatch.setattr(fa, "flash_attention_tpu_available", lambda: True)
    from paddle_tpu.inference.paging import pages_for_budget
    from paddle_tpu.inference.replica import _spec_config
    from paddle_tpu.models import llama_init_params
    from paddle_tpu.models.llama_paged import (
        init_paged_kv_cache, llama_paged_decode_burst,
        llama_paged_prefill_slot, page_bytes, paged_kv_read)
    from perfbench import harness
    from perfbench.families import exaone_moe
    cell = harness.load_cell("k-exaone-236b.reasoning-batch", rehearse=False)
    eng = cell["traffic"]["engine"]
    cfg = _spec_config({"config": exaone_moe.model_spec(cell["cfg"],
                                                        eng["max_len"])})
    cfg = dataclasses.replace(
        cfg, num_hidden_layers=3,
        layer_types=(cfg.SLIDING, cfg.SLIDING, cfg.FULL),
        mlp_layer_types=(cfg.DENSE, cfg.SPARSE, cfg.SPARSE))
    ps, B = eng["page_size"], eng["max_batch"]
    assert paged_kv_read(cfg, ps) == "kernel"
    pages = pages_for_budget(eng["pool_hbm_bytes"], page_bytes(cfg, ps))
    pages = min(pages, B * eng["max_len"] // ps + 1)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda key: llama_init_params(cfg, key), jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(
        lambda: init_paged_kv_cache(cfg, pages, ps, max_batch=B)))
    assert cache["win_k"][0].shape == (B, 128, 8, 128)
    sds = _shapes(one_chip, "int32")
    key = on_chip(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    if program == "decode_burst":
        text = llama_paged_decode_burst.lower(
            params, cache, sds((B, eng["page_buckets"][-1])), sds((B,)),
            sds((B,)), sds((B,), "bool"), sds((B,)), sds(()), key,
            config=cfg, n=eng["burst"], kv_read="kernel",
            interpret=False).compile().as_text()
        want = {"paged_decode_attention": 3, "paged_kv_scatter": 3, "gmm": 6}
    else:
        bucket = eng["prompt_buckets"][-1]
        text = llama_paged_prefill_slot.lower(
            params, cache, sds((bucket,)), sds((bucket // ps,)), sds(()),
            key, config=cfg, kv_read="kernel", interpret=False,
            slot=sds(())).compile().as_text()
        want = {"flash_fwd": 3, "paged_kv_scatter": 1, "gmm": 6}
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    for name, n in want.items():
        assert len([ln for ln in calls if name in ln]) == n, (name, len(calls))
    assert not _pool_copies(text, pages)
    entry = text[text.index("\nENTRY"):]
    assert not [ln for ln in entry.splitlines()
                if " copy(" in ln and "bf16[2,16," in ln]


# ------------- the burst's weights a layer at a time, at the cells' depth (PR 35)

@pytest.mark.parametrize("workload", [
    "internlm2-1.8b.longctx-batch", "olmo-hybrid-7b.longdoc-batch",
    "k-exaone-236b.reasoning-batch"])
def test_burst_of_a_cell_copies_no_whole_weight_matrix(one_chip,
                                                       no_compile_cache,
                                                       workload):
    """A serving cell's burst at the cell's widths, slots, page bucket, pool
    and FULL depth (at 4 layers the stacks fit VMEM and XLA does something
    else). THE CONTROL, which proves the check sees: handed the layer stacks
    in the default layout (what the engine compiled until PR 35), the
    compiled burst transposes the whole stack of every projection whose
    output is split into heads at once (`heads_at_once_leaves`) at its entry
    and slices each layer's matrix out of that copy into a buffer of its own
    at every decode step: 0.40 / 0.65 / 1.0 GB a step. THE ENGINE'S: handed
    those leaves a layer at a time with their layout left to the compiler
    (`per_layer_weights`, `burst_for_layouts`), it asks each matrix
    column-major and no such copy is left, at the entry or in the loop: this
    is what holds the rule to the compiler."""
    from paddle_tpu.models.llama import heads_at_once_leaves
    from tools import burst_weight_copies as bwc
    cfg, _, params, _ = bwc._cell(workload, "stacks")
    named = [n for n in heads_at_once_leaves(cfg) if n in params]
    per_step = sum(params[n].size * 2 for n in named)
    shapes = {"bf16[1,%d,%d]" % params[n].shape[1:] for n in named}

    control = bwc.copies(bwc.burst_program(workload, one_chip,
                                           "stacks").as_text())
    loop = [r for r in control if r["where"] == "loop"]
    assert {r["shape"] for r in loop} == shapes, control
    # the layers whose slice lands in VMEM at once (2-5 of them) not counted
    assert 0.6 * per_step <= sum(r["bytes"] for r in loop) <= per_step
    assert [r for r in control if r["where"] == "entry"], control

    burst = bwc.burst_program(workload, one_chip, "engine")
    assert bwc.asked_layouts(burst) == dict.fromkeys(named, (1, 0))
    assert bwc.copies(burst.as_text()) == []


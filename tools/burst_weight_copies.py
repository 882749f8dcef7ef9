#!/usr/bin/env python
"""Which whole weight matrices do a serving cell's paged programs COPY?

    python3 tools/burst_weight_copies.py <cell> [--form engine|stacks]

Compiles, for one DESCRIBED v5e chip (no chip needed, nothing runs), the
burst and the widest bucketed prefill of a benchmark cell at the cell's own
widths, slots, page bucket, pool and full depth, and lists every operation
that writes a whole layer's weight matrix (>= 4 MiB) to HBM:

  entry  ``copy(%params...)`` of a stacked leaf: once a call (a burst of n
         steps, a prompt);
  loop   a ``kLoop`` fusion under ``.../while/body/.../slice`` (the burst's
         step; the layer scan of a one-kind model's prefill) or a ``copy``
         there: once a step, or a layer.

``--form engine`` (default) is what ``ContinuousBatcher`` compiles since
ISSUE 35: the leaves of ``heads_at_once_leaves`` a layer at a time
(``per_layer_weights``) in the layouts the burst's own compile asks for
(``burst_for_layouts``); ``--form stacks`` is the tree as
``llama_init_params`` stacks it, in the default layout: what the engine
compiled before, the control of ``tests/test_tpu_compile.py``. Prints one
JSON object. One process at a time may hold the TPU library.
"""
from __future__ import annotations

import json
import math
import os
import re
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

CELLS = ("internlm2-1.8b.longctx-batch", "olmo-hybrid-7b.longdoc-batch",
         "k-exaone-236b.reasoning-batch")
MIN_BYTES = 4 << 20
_ARRAY = re.compile(r"(bf16|f32|f16|s8)\[([\d,]+)\]\{([^}]*)\}")
_SIZE = {"bf16": 2, "f16": 2, "f32": 4, "s8": 1}


def cell_model(workload: str):
    """(the cell's model as the program's LlamaConfig, at full depth; the
    mix's engine settings), from the benchmark's own files."""
    from paddle_tpu.inference.replica import _spec_config
    from perfbench import families, harness
    cell = harness.load_cell(workload, rehearse=False)
    cfg, eng = cell["cfg"], cell["traffic"]["engine"]
    fam = families.of(cfg)
    if cfg["family"] == "llama":
        return fam.llama_config(cfg, eng["max_len"]), eng
    return _spec_config({"config": fam.model_spec(cfg, eng["max_len"])}), eng


def copies(text: str, min_bytes: int = MIN_BYTES) -> list:
    """[{op, where, arrays, bytes, shape}] of the whole-matrix writes in a
    compiled program's text: ``bytes`` one execution of the operation
    writes to HBM (outputs in VMEM, ``S(1)``, are not counted)."""
    out = []
    for ln in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = (.*?) (copy|fusion)\(", ln)
        if not m:
            continue
        name, types, opcode = m.groups()
        op_name = (re.search(r'op_name="([^"]*)"', ln) or [None, ""])[1]
        loop = "/while/body/" in op_name
        if opcode == "copy":
            if not (loop or "copy(%params" in ln):
                continue
        elif "kind=kLoop" not in ln or not op_name.endswith("/slice"):
            continue
        big = [(dt, dims) for dt, dims, layout in _ARRAY.findall(types)
               if "S(1)" not in layout and len(dims.split(",")) in (2, 3)
               and _SIZE[dt] * math.prod(map(int, dims.split(",")))
               >= min_bytes]
        if big:
            out.append({
                "op": name, "where": "loop" if loop else "entry",
                "arrays": len(big), "shape": f"{big[0][0]}[{big[0][1]}]",
                "bytes": sum(_SIZE[dt] * math.prod(map(int, d.split(",")))
                             for dt, d in big)})
    return out


def _cell(workload: str, form: str):
    """(cfg, engine settings, abstract params in ``form``, abstract cache)."""
    import jax
    import paddle_tpu  # noqa: F401  (jax_enable_x64, as the engine runs)
    from paddle_tpu.inference.paging import pages_for_budget
    from paddle_tpu.models import llama_init_params
    from paddle_tpu.models.llama_paged import (
        init_paged_kv_cache, page_bytes, per_layer_weights)
    cfg, eng = cell_model(workload)
    ps, B = eng["page_size"], eng["max_batch"]
    pages = min(pages_for_budget(eng["pool_hbm_bytes"], page_bytes(cfg, ps)),
                B * eng["max_len"] // ps + 1)
    params = jax.eval_shape(lambda k: llama_init_params(cfg, k),
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(
        lambda: init_paged_kv_cache(cfg, pages, ps, max_batch=B))
    if form == "engine":
        params = per_layer_weights(params, cfg)
    return cfg, eng, params, cache


def burst_program(workload: str, sharding, form: str = "engine"):
    """The cell's burst compiled for ``sharding``'s described device, as the
    engine compiles it (``form`` "engine": per-layer leaves, their layouts
    the compiler's) or as it did (``"stacks"``)."""
    from paddle_tpu.models.llama_paged import burst_for_layouts
    cfg, eng, params, cache = _cell(workload, form)
    return burst_for_layouts(
        params, cache, eng["max_batch"], eng["page_buckets"][-1], sharding,
        config=cfg, n=eng["burst"], kv_read="kernel", interpret=False)


def asked_layouts(burst) -> dict:
    """{leaf: major_to_minor} of the per-layer leaves of a compiled burst."""
    return {k: v[0].layout.major_to_minor
            for k, v in burst.input_formats[0][0].items()
            if isinstance(v, tuple)}


def prefill_program(workload: str, sharding, form: str = "engine",
                    burst=None):
    """The cell's widest bucketed prefill, handed what the engine hands it:
    a model with a layer pattern the per-layer leaves in the formats
    ``burst`` asked for, a model of one layer kind the stacks."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.layout import Format
    from paddle_tpu.models.llama_paged import llama_paged_prefill_slot
    from paddle_tpu.ops import flash_attention as fa
    model = cell_model(workload)[0]
    if model.layer_types is None and model.mlp_layer_types is None:
        form = "stacks"
    cfg, eng, params, cache = _cell(workload, form)
    asked = burst.input_formats[0][0] if burst is not None else {}
    sds = lambda s, d=jnp.int32, f=sharding: jax.ShapeDtypeStruct(  # noqa
        s, d, sharding=f)
    put = lambda t: jax.tree.map(lambda a: sds(a.shape, a.dtype), t)  # noqa
    params = {k: tuple(sds(a.shape, a.dtype,
                           Format(asked[k][i].layout, sharding))
                       for i, a in enumerate(v))
              if isinstance(v, tuple) else put(v) for k, v in params.items()}
    bucket, ps = eng["prompt_buckets"][-1], eng["page_size"]
    # the flash kernel's platform gate asks jax.default_backend(), which is
    # this process's CPU: steered here, as tests/test_tpu_compile.py does
    gate, fa.flash_attention_tpu_available = \
        fa.flash_attention_tpu_available, lambda: True
    try:
        return llama_paged_prefill_slot.lower(
            params, put(cache), sds((bucket,)), sds((bucket // ps,)),
            sds(()), put(jax.eval_shape(lambda: jax.random.PRNGKey(0))),
            config=cfg, kv_read="kernel", interpret=False,
            slot=sds(()) if cfg.slot_state else None).compile()
    finally:
        fa.flash_attention_tpu_available = gate


def report(workload: str, sharding, form: str = "engine") -> dict:
    burst = burst_program(workload, sharding, form)
    texts = {"burst": burst.as_text(),
             "prefill": prefill_program(workload, sharding, form,
                                        burst).as_text()}
    out = {"cell": workload, "form": form,
           "asked_layouts": {k: str(v)
                             for k, v in asked_layouts(burst).items()}}
    for prog, text in texts.items():
        rows = copies(text)
        out[prog] = {"copies": rows, **{
            f"{w}_bytes": sum(r["bytes"] for r in rows if r["where"] == w)
            for w in ("entry", "loop")}}
    b, n = out["burst"], cell_model(workload)[1]["burst"]
    b["bytes_a_step"] = b["loop_bytes"]
    b["bytes_a_burst"] = b["entry_bytes"] + n * b["loop_bytes"]
    return out


def main(argv=None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    form = "engine"
    if "--form" in args:
        i = args.index("--form")
        form = args[i + 1]
        del args[i:i + 2]
    if len(args) != 1 or args[0] not in CELLS or form not in ("engine",
                                                              "stacks"):
        print(__doc__, file=sys.stderr)
        return 2
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    json.dump(report(args[0], SingleDeviceSharding(topo.devices[0]), form),
              sys.stdout, indent=1)
    print()
    return 0


if __name__ == "__main__":
    sys.exit(main())

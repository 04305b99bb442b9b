"""The kernels of the main paths compiled for the chip, without the chip.

The Pallas interpreter (every other kernel test) cannot see what Mosaic
refuses: a copy too narrow for its tiling, a slice off the tile grid, more
VMEM than a kernel may hold. The TPU compiler is installed here and compiles
for a v5e that is described, not attached — about two seconds a kernel — so
these cases hold the paged and the latent kernel to the benchmark cells' call
shapes at the page count each one's own rule picks, and the flash kernels to the
training cells' — alone on one chip, and inside a ``ShardedTrainer`` step over
four — and the serving sampler to a ``conditional`` around its sort. All of
them live in this one file: the worker
that runs it is the one process that loads the TPU's library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from k8s_distributed_deeplearning_tpu.ops import (pallas_flash, pallas_latent_attn,
                                                  pallas_paged_attn)


@pytest.fixture(scope="module")
def chips():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A program compiled for a described chip is written to the persistent
    # cache but cannot be read back without one: keep these out of it.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(chips):
    return SingleDeviceSharding(chips[0])


# mistral-7b widths as the backlog cell serves them: 32 q / 8 kv heads x 128,
# 32-token pages, a 128-block table over a 2,560-page pool (+ scratch); and
# what one of four tp shards of the smoke's `small` model holds: 3 q heads on
# ONE hd-64 KV head, a pool half a lane tile wide.
MISTRAL = (32, 8, 128)
SMALL_TP4 = (3, 1, 64)
# lfm2-8b-a1b's attention layers as the chat-backlog-wide cell serves them:
# 32 q heads in groups of 4 on 8 hd-64 KV heads (a pool 512 lanes wide, each
# head a 64-lane slice of a tile), 128 slots a decode call.
LFM2 = (32, 8, 64)
# nemotron-3-super's one attention layer in eleven: 32 q heads in groups of 16
# on 2 hd-128 KV heads (a pool 256 lanes wide).
NEMOTRON = (32, 2, 128)
# ai21-jamba2-3b's two attention layers in 28: 20 q heads on ONE hd-128 KV
# head (a group of 20: a decode call's slab is 20 query rows, no multiple of 8).
JAMBA = (20, 1, 128)
PAGE_TOKENS, N_BLOCKS, NUM_PAGES = 32, 128, 2561


@pytest.mark.parametrize("heads,sq,b,quant,pages", [
    (MISTRAL, 1, 32, False, None),      # the decode program
    (MISTRAL, 5, 32, False, None),      # a speculative verify window
    (MISTRAL, 128, 1, False, None),     # the prefill chunk programs
    (MISTRAL, 1, 32, True, None),       # int8 pages + their scale blocks
    (MISTRAL, 128, 1, True, None),
    (MISTRAL, 1, 32, False, 3),         # a tile off the lane grid, ragged tail
    (SMALL_TP4, 1, 8, False, None),     # a page a cell, by the block pipeline
    (SMALL_TP4, 128, 1, True, None),
    (LFM2, 1, 128, False, None),        # head size 64 at 128 rows
    (LFM2, 128, 1, False, None),
    (LFM2, 512, 1, False, None),        # a 512-token chunk: 4 query blocks
    (NEMOTRON, 512, 1, False, None),    # groups of 16: 16 blocks of 32
    (JAMBA, 1, 128, False, None),       # a group of 20 at 128 rows
    (JAMBA, 512, 1, False, None),       # groups of 20: 32 blocks of 16
], ids=["decode", "verify5", "chunk128", "decode-int8", "chunk128-int8",
        "decode-3pages", "narrow-decode", "narrow-chunk128-int8",
        "hd64-decode-128rows", "hd64-chunk128", "hd64-chunk512",
        "group16-chunk512", "group20-decode-128rows", "group20-chunk512"])
def test_paged_kernel_compiles_for_v5e(one_chip, heads, sq, b, quant, pages):
    H, HKV, HD = heads
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    page_dtype = jnp.int8 if quant else jnp.bfloat16
    pool = sds((NUM_PAGES, PAGE_TOKENS, HKV * HD), page_dtype)
    scale = sds((NUM_PAGES, PAGE_TOKENS, HKV), jnp.float32) if quant else None

    def call(q, pk, pv, tables, pos, ks, vs):
        return pallas_paged_attn.paged_decode_attention(
            q, pk, pv, tables, pos, k_scale=ks, v_scale=vs,
            pages_per_cell=pages, interpret=False)
    compiled = jax.jit(call).lower(
        sds((b, sq, H, HD), jnp.bfloat16), pool, pool,
        sds((b, N_BLOCKS), jnp.int32), sds((b, sq), jnp.int32),
        scale, scale).compile()
    assert "paged_attn" in compiled.as_text()


# sarvam-105b's latent pool as the docs-backlog cell serves it: 64 heads on one
# 640-lane row a token (512 latent + 64 rope + 64 pad), 64-token pages, a
# 272-block table over a 7,000-page pool (+ scratch).
@pytest.mark.parametrize("sq,pages", [(1, None), (4, None), (1, 3)],
                         ids=["decode", "verify4", "decode-3pages"])
def test_latent_kernel_compiles_for_v5e(one_chip, sq, pages):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def call(q, pool, tables, pos):
        return pallas_latent_attn.latent_decode_attention(
            q, pool, tables, pos, rank=512, softmax_scale=0.135,
            pages_per_cell=pages, interpret=False)
    compiled = jax.jit(call).lower(
        sds((32, sq, 64, 640), jnp.bfloat16), sds((7001, 64, 640), jnp.bfloat16),
        sds((32, 272), jnp.int32), sds((32, sq), jnp.int32)).compile()
    assert "latent_attn" in compiled.as_text()


def test_latent_chunk_kernel_compiles_for_v5e(one_chip):
    """The expanded form of a 1,024-token chunk over the cell's 17,408
    gathered cache positions: 4 heads a cell, blocks of 512, inside the VMEM
    limit it asks for (the compiler's own count of the scoped VMEM the
    kernel uses)."""
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    call = lambda qn, qr, lat, wuk, wuv, pos: pallas_latent_attn.latent_chunk_attention(
        qn, qr, lat, wuk, wuv, pos, rank=512, softmax_scale=0.135, interpret=False)
    compiled = jax.jit(call).lower(
        sds((1, 1024, 64, 128), jnp.bfloat16), sds((1, 1024, 64, 64), jnp.bfloat16),
        sds((1, 17408, 640), jnp.bfloat16), sds((512, 64, 128), jnp.bfloat16),
        sds((512, 64, 128), jnp.bfloat16), sds((1, 1024), jnp.int32)).compile()
    text = compiled.as_text()
    assert "latent_chunk_attn" in text
    kernel = next(line for line in text.splitlines()
                  if "latent_chunk_attn/pallas_call" in line and "custom-call" in line)
    size = lambda key: int(re.search(rf'"{key}":\[{{"memory_space":"1","offset":"0","size":"(\d+)"',
                                     kernel).group(1))
    assert size("scoped_memory_configs") == 2 * pallas_paged_attn.VMEM_LIMIT_BYTES
    assert 0 < size("used_scoped_memory_configs") <= 2 * pallas_paged_attn.VMEM_LIMIT_BYTES


def test_a_576_lane_latent_pool_is_refused_by_mosaic(one_chip):
    """Why a cached token is padded to 640 lanes: the chip lays a 576-lane
    array out 640 wide anyway, and Mosaic copies whole 128-lane tiles."""
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    call = lambda q, pool, tables, pos: pallas_latent_attn.latent_decode_attention(
        q, pool, tables, pos, rank=512, softmax_scale=0.135, interpret=False)
    with pytest.raises(Exception, match="aligned to tiling"):
        jax.jit(call).lower(
            sds((32, 1, 64, 576), jnp.bfloat16), sds((7001, 64, 576), jnp.bfloat16),
            sds((32, 272), jnp.int32), sds((32, 1), jnp.int32)).compile()


@pytest.mark.parametrize("b,seq,heads,causal,kernels", [
    # bert-base.mlm-s512: pairs of 64-lane heads, the sequence resident
    (16, 512, (12, 12, 64), False, ["flash_attn_fwd", "flash_attn_bwd"]),
    (16, 512, (12, 12, 64), True, ["flash_attn_fwd", "flash_attn_bwd"]),
    # pairs, streamed in blocks; the smoke's two shapes (folded view)
    (4, 2048, (12, 12, 64), False, ["flash_attn_fwd", "flash_attn_dq", "flash_attn_dkv"]),
    (2, 2048, (12, 4, 64), True, ["flash_attn_fwd", "flash_attn_dq", "flash_attn_dkv"]),
    (2, 2048, MISTRAL, True, ["flash_attn_fwd", "flash_attn_dq", "flash_attn_dkv"]),
], ids=["bert-s512", "bert-s512-causal", "pairs-s2048", "gqa-hd64-s2048", "gqa-hd128-s2048"])
def test_flash_kernels_compile_for_v5e(one_chip, b, seq, heads, causal, kernels):
    H, HKV, HD = heads
    sds = lambda h: jax.ShapeDtypeStruct((b, seq, h, HD), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        o = pallas_flash.flash_attention(q, k, v, causal=causal, interpret=False)
        return jnp.sum(o.astype(jnp.float32))
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        sds(H), sds(HKV), sds(HKV)).compile().as_text()
    assert [k for k in ("flash_attn_fwd", "flash_attn_bwd", "flash_attn_dq", "flash_attn_dkv")
            if k in text] == kernels


def test_sharded_step_runs_the_flash_kernel_on_each_chips_own_rows(chips, monkeypatch):
    """A Pallas call has no partition rule: unwrapped, every chip of a
    data-parallel mesh would all-gather the batch and attend all of it. Built
    by ``ShardedTrainer`` with no ``attention_fn`` (as the benchmark builds
    it), the step's kernels take 2 of the 8 rows each and nothing is
    gathered."""
    import flax.linen as nn
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import numpy as np

    from k8s_distributed_deeplearning_tpu.models import bert
    from k8s_distributed_deeplearning_tpu.parallel import sharding
    monkeypatch.setattr(pallas_flash, "on_tpu", lambda: True)     # compile, not interpret
    mesh = Mesh(np.array(chips), ("data",))
    cfg = bert.config_tiny(dim=128, n_heads=2, n_layers=2, mlp_dim=256, max_seq_len=128,
                           attention_impl="flash", dtype=jnp.bfloat16)
    model = bert.BertMLM(cfg)

    def loss(p, batch, r):
        inputs, targets, w = bert.mask_tokens(batch["tokens"], r, vocab_size=cfg.vocab_size,
                                              mask_id=3, mask_prob=0.15)
        return bert.loss_fn(model, p, {"inputs": inputs, "targets": targets, "weights": w})
    trainer = sharding.ShardedTrainer(loss, optax.sgd(0.1), mesh)

    def make_state(r):
        params = model.init(r, jnp.zeros((1, 8), jnp.int32))["params"]
        return sharding.TrainState(params=params, opt_state=trainer.optimizer.init(params),
                                   step=jnp.zeros((), jnp.int32))
    key = jax.eval_shape(lambda: jax.random.key(0))
    with mesh, nn.logical_axis_rules(trainer.rules):
        abstract = jax.eval_shape(make_state, key)
    placed = sharding.state_shardings(abstract, mesh, trainer.rules)
    trainer._state_sh = placed
    state = jax.tree.map(lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
                         abstract, placed)
    batch = {"tokens": jax.ShapeDtypeStruct(
        (8, 128), jnp.int32, sharding=sharding.batch_sharding(mesh, trainer.rules))}
    key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=NamedSharding(mesh, P()))
    text = trainer.make_step(donate=False).lower(state, batch, key).compile().as_text()
    calls = [l for l in text.splitlines() if 'custom_call_target="tpu_custom_call"' in l]
    assert len(calls) == 2 and "flash_attn_fwd" in text and "flash_attn_bwd" in text
    for line in calls:
        operands = re.findall(r"bf16\[(\d+),128,128\]", line)
        assert operands and set(operands) == {"2"}, line
    assert "all-gather" not in text


def _reached_outside_conditionals(text, instruction, containing=""):
    """Names of the computations of compiled module *text* that hold an
    *instruction* (``"sort"``; on a line that has *containing*, a width) AND
    are reached from ENTRY by a path with no ``conditional`` on it: what the
    program runs whatever its operands are."""
    blocks, entry, name = {}, None, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$", line)
        if head:
            name = head.group(2)
            blocks[name] = []
            entry = name if head.group(1) else entry
        elif name is not None:
            blocks[name].append(line)
    assert entry is not None
    seen, todo = set(), [entry]
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in blocks[name]:
            if re.search(r"\bconditional\(", line):
                continue
            todo.extend(n for n in re.findall(r"%([\w.\-]+)", line) if n in blocks)
    return sorted(n for n in seen if any(
        re.search(rf"\b{instruction}\(", line) and containing in line
        for line in blocks[n]))


def test_the_sampler_sorts_only_inside_a_conditional(one_chip):
    """``_sample_slots`` alone at the Mistral cell's decode shape (the `lfm2`
    cell's is held inside its whole decode program, below): the TPU compiler
    keeps the branch a ``conditional`` — no ``select`` over both sides — and
    no computation on the unconditional path sorts."""
    from k8s_distributed_deeplearning_tpu.serve import engine as E
    rows, vocab = 32, 32768
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    text = jax.jit(E._sample_slots).lower(
        sds((rows, vocab), jnp.float32), sds((rows,), jnp.float32),
        sds((rows,), jnp.int32), sds((rows,), jnp.float32),
        sds((rows, 2), jnp.uint32)).compile().as_text()
    assert " sort(" in text and " conditional(" in text
    assert _reached_outside_conditionals(text, "sort") == []
    assert _reached_outside_conditionals(text, "conditional")     # the walk sees ENTRY


def _conv_moe_cell(one_chip):
    """(model, params, cache, engine options, sds) of
    `lfm2-8b-a1b-d14.chat-backlog-wide` as shapes placed on a described chip:
    14 unrolled layers at the published widths, the page pool of the three
    attention layers and the state arena of the eleven convolutions."""
    import flax.linen as nn

    from benchmarks.harness import family_conv_moe as fam
    from benchmarks.harness import manifest as M
    from k8s_distributed_deeplearning_tpu.models.transformer import PatternLM
    cell = M.Cell(M.load_manifest(), "lfm2-8b-a1b-d14.chat-backlog-wide")
    cfg, eng = cell.config, cell.options["engine"]
    slots, pt, pages = eng["num_slots"], eng["page_tokens"], eng["kv_pool_pages"] + 1
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    model = PatternLM(*fam.program_config(cfg, eng["max_seq_len"]))
    params = jax.tree.map(
        lambda a: sds(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda: nn.meta.unbox(model.init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])))
    lanes = cfg["num_key_value_heads"] * cfg["head_dim"]
    cache = {"transformer": {f"block_{i}": {"attn": (
        {"conv_state": sds((slots, cfg["conv_L_cache"] - 1, cfg["hidden_size"]), jnp.bfloat16)}
        if kind == "conv" else
        {"cached_key": sds((pages, pt, lanes), jnp.bfloat16),
         "cached_value": sds((pages, pt, lanes), jnp.bfloat16)})}
        for i, kind in enumerate(cfg["layer_types"][:cfg["num_hidden_layers"]])}}
    return model, params, cache, eng, sds


def test_the_conv_moe_cells_decode_program_compiles_and_fits_one_chip(one_chip, monkeypatch):
    """`lfm2-8b-a1b-d14.chat-backlog-wide`'s decode program whole, at the
    published widths and the cell's engine options, from shapes alone: 128
    slots through 14 unrolled layers — the paged kernel at head size 64 in the
    3 attention layers, the state arena advanced in place, every expert in the
    dense form — and what it holds (9.33 GB of weights, 3.22 GB of pages, the
    arena) beside its temporaries inside one v5e's 16 GB."""
    from k8s_distributed_deeplearning_tpu.ops import pallas_gmm
    from k8s_distributed_deeplearning_tpu.serve import engine as E
    for mod in (pallas_paged_attn, pallas_gmm):
        monkeypatch.setattr(mod, "on_tpu", lambda: True)         # compile, not interpret
    model, params, cache, eng, sds = _conv_moe_cell(one_chip)
    slots, pt = eng["num_slots"], eng["page_tokens"]
    i32, f32 = (lambda *s: sds(s, jnp.int32)), (lambda *s: sds(s, jnp.float32))
    compiled = E._decode_program.lower(
        model, params, cache, i32(slots), i32(slots),
        i32(slots, eng["max_seq_len"] // pt), f32(slots), i32(slots), f32(slots),
        sds((slots, 2), jnp.uint32)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert text.count("paged_attn") >= 3 and "moe_gmm" not in text
    # The sampler's sort over [128, 65536] is there, and only a step with a
    # sampling row runs it.
    assert " sort(" in text and _reached_outside_conditionals(text, "sort") == []
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 12.5e9 < mem.argument_size_in_bytes < 12.7e9 and held < 14e9
    assert mem.alias_size_in_bytes > 3.2e9                      # pool and arena in place


def test_the_conv_moe_cells_chunk_program_attends_through_the_kernel(one_chip, monkeypatch):
    """The same cell's intermediate CHUNK program, 512 tokens of one slot: the
    three attention layers attend through the paged kernel in blocks of 128
    queries — no gather of the 4,096-position table and no ``[32, 512, 4096]``
    float32 scores in HBM — and the experts take the grouped products."""
    from k8s_distributed_deeplearning_tpu.ops import pallas_gmm
    from k8s_distributed_deeplearning_tpu.serve import engine as E
    for mod in (pallas_paged_attn, pallas_gmm):
        monkeypatch.setattr(mod, "on_tpu", lambda: True)         # compile, not interpret
    model, params, cache, eng, sds = _conv_moe_cell(one_chip)
    c, blocks = eng["prefill_chunk_tokens"], eng["max_seq_len"] // eng["page_tokens"]
    assert c == 512
    text = E._chunk_program.lower(
        model, params, cache, sds((1, c), jnp.int32), sds((1, blocks), jnp.int32),
        sds((), jnp.int32), sds((), jnp.int32)).compile().as_text()
    assert text.count("paged_attn") >= 3 and "moe_gmm" in text
    assert "f32[32,512,4096]" not in text and "f32[1,32,512,4096]" not in text
    # The expert layers' silu(gate) * up is made inside the grouped kernel, its
    # two weight blocks double-buffered inside the VMEM limit (else Mosaic
    # refuses the compile): no pass of its own over the 6,144 padded rows. 11 of
    # 12 layers: the last one's experts feed only the head, which an
    # intermediate chunk does not run.
    assert _calls(text, "moe_gmm_act") == 11
    assert _hidden_passes(text, 6144, 1792) == []


def _hidden_passes(text, rows, width):
    """Fusions of compiled module *text* whose result is a bf16 ``[rows,
    width]`` array: the experts' hidden activation as an XLA pass of its own."""
    return [l.strip()[:160] for l in text.splitlines()
            if re.search(rf"= bf16\[{rows},{width}\][^ ]* fusion\(", l)]


def _ssm_moe_cell(one_chip):
    """(model, params, cache, engine options, sds) of
    `nemotron-3-super-ep4-d11.chat-backlog-wide` as shapes placed on a described
    chip: 11 unrolled layers at the published widths, the page pool of the one
    attention layer and the state arena of the five Mamba-2 layers."""
    import flax.linen as nn

    from benchmarks.harness import family_ssm_moe as fam
    from benchmarks.harness import manifest as M
    from benchmarks.harness import reference_ssm_moe as ref
    from k8s_distributed_deeplearning_tpu.models.transformer import PatternLM
    cell = M.Cell(M.load_manifest(), "nemotron-3-super-ep4-d11.chat-backlog-wide")
    cfg, eng = cell.config, cell.options["engine"]
    slots, pt, pages = eng["num_slots"], eng["page_tokens"], eng["kv_pool_pages"] + 1
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    model = PatternLM(*fam.program_config(cfg, eng["max_seq_len"]))
    params = jax.tree.map(
        lambda a: sds(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda: nn.meta.unbox(model.init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])))
    lanes = cfg["num_key_value_heads"] * cfg["head_dim"]
    h, p, g, n, k = ref.mamba_sizes(cfg)
    cache = {"transformer": {}}
    for i, kind in enumerate(ref.pattern(cfg)):
        if kind == "M":
            cache["transformer"][f"block_{i}"] = {"attn": {
                "conv_state": sds((slots, k - 1, h * p + 2 * g * n), jnp.bfloat16),
                "ssm_state": sds((slots, h // 2 * n, 2 * p), jnp.float32)}}
        elif kind == "*":
            cache["transformer"][f"block_{i}"] = {"attn": {
                "cached_key": sds((pages, pt, lanes), jnp.bfloat16),
                "cached_value": sds((pages, pt, lanes), jnp.bfloat16)}}
    return model, params, cache, eng, sds


def _arena_sized_copies(text, elems=128 * 8192 * 128):
    """Lines of compiled module *text* that select over or copy an f32 array
    the size of one layer's state arena."""
    shape = rf"f32\[128,8192,128\]"
    return [l.strip()[:160] for l in text.splitlines()
            if re.search(rf"= {shape}[^ ]* (select|copy)\(", l)]


def test_the_ssm_update_kernel_compiles_for_v5e(one_chip):
    """`ssm_update` at the cell's call shape — 128 slots x 128 heads x 64 x
    128 float32, two heads to a row — aliases the arena to its output and
    holds next to nothing beside it."""
    from k8s_distributed_deeplearning_tpu.ops import pallas_ssm
    b, h, p, g, n = 128, 128, 64, 8, 128
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    rows, lanes = pallas_ssm.state_shape(h, p, n, g)
    assert (rows, lanes) == (8192, 128)
    compiled = jax.jit(
        lambda s, *a: pallas_ssm.ssm_update(s, *a, interpret=False), donate_argnums=0
    ).lower(sds((b, rows, lanes), jnp.float32), sds((b, h, p), jnp.bfloat16),
            sds((b, h), jnp.float32), sds((h,), jnp.float32),
            sds((b, g, n), jnp.bfloat16), sds((b, g, n), jnp.bfloat16),
            sds((b,), jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    assert "ssm_update" in compiled.as_text()
    assert mem.alias_size_in_bytes == b * rows * lanes * 4
    assert mem.temp_size_in_bytes < 2 ** 20


def test_the_ssm_moe_cells_decode_program_compiles_and_fits_one_chip(one_chip, monkeypatch):
    """`nemotron-3-super-ep4-d11.chat-backlog-wide`'s decode program whole, from
    shapes alone: 128 slots through 11 unrolled layers — `ssm_update` in the
    five Mamba-2 layers with the arena in place (no select over and no copy of
    a layer's 0.5 GB of state), the paged kernel at 16 query heads a KV head in
    the attention layer, the held experts in the dense form — and what it
    holds (9.30 GB of weights, 2.72 GB of state, 0.54 GB of pages) beside its
    temporaries inside one v5e's 16 GB."""
    from k8s_distributed_deeplearning_tpu.models import transformer as T
    from k8s_distributed_deeplearning_tpu.ops import pallas_gmm, pallas_ssm
    from k8s_distributed_deeplearning_tpu.serve import engine as E
    for mod in (pallas_paged_attn, pallas_gmm, pallas_ssm, T):
        monkeypatch.setattr(mod, "on_tpu", lambda: True)         # compile, not interpret
    model, params, cache, eng, sds = _ssm_moe_cell(one_chip)
    slots, pt = eng["num_slots"], eng["page_tokens"]
    i32, f32 = (lambda *s: sds(s, jnp.int32)), (lambda *s: sds(s, jnp.float32))
    compiled = E._decode_program.lower(
        model, params, cache, i32(slots), i32(slots),
        i32(slots, eng["max_seq_len"] // pt), f32(slots), i32(slots), f32(slots),
        sds((slots, 2), jnp.uint32)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert text.count("ssm_update") >= 5 and "paged_attn" in text and "moe_gmm" not in text
    assert _arena_sized_copies(text) == []
    # The sampler's sort over [128, 32768] is there, and only a step with a
    # sampling row runs it; what sorts on every step is the router's top-22 of
    # 512 (one `lax.top_k` a layer), nothing of the vocabulary's width.
    assert "32768" in "".join(l for l in text.splitlines() if " sort(" in l)
    assert _reached_outside_conditionals(text, "sort", containing="32768") == []
    assert _reached_outside_conditionals(text, "sort") != []         # the top-22 of 512
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 12.4e9 < mem.argument_size_in_bytes < 12.8e9 and held < 14.5e9
    assert mem.alias_size_in_bytes > 3.2e9                      # arena and pool in place

def test_the_ssm_moe_cells_chunk_program_makes_the_experts_activation_in_the_kernel(
        one_chip, monkeypatch):
    """The same cell's intermediate CHUNK program, 512 tokens of one slot: the
    expert layers take the grouped products at 27,648 padded rows and the
    squared ReLU is made inside the first of them — no
    ``maximum_multiply_fusion`` over ``bf16[27648,2688]``. Four of the five:
    the last layer's experts feed only the head, which such a chunk does not
    run."""
    from k8s_distributed_deeplearning_tpu.models import transformer as T
    from k8s_distributed_deeplearning_tpu.ops import pallas_gmm, pallas_ssm
    from k8s_distributed_deeplearning_tpu.serve import engine as E
    for mod in (pallas_paged_attn, pallas_gmm, pallas_ssm, T):
        monkeypatch.setattr(mod, "on_tpu", lambda: True)         # compile, not interpret
    model, params, cache, eng, sds = _ssm_moe_cell(one_chip)
    c, blocks = eng["prefill_chunk_tokens"], eng["max_seq_len"] // eng["page_tokens"]
    text = E._chunk_program.lower(
        model, params, cache, sds((1, c), jnp.int32), sds((1, blocks), jnp.int32),
        sds((), jnp.int32), sds((), jnp.int32)).compile().as_text()
    assert _calls(text, "moe_gmm_act") == 4 and _calls(text, "moe_gmm") == 4   # W_down
    assert _hidden_passes(text, 27648, 2688) == []


def test_the_selective_kernels_compile_for_v5e(one_chip):
    """`selective_update` at the decode call's shape — 128 slots x 16 x 5,120
    float32 — aliases the arena to its output; `selective_scan` at a 512-token
    chunk's keeps its state in VMEM: neither holds a temporary of the
    ``[L, N, E]`` size an associative scan would write."""
    from k8s_distributed_deeplearning_tpu.ops import pallas_selective_scan as S
    b, n, e, length = 128, 16, 5120, 512
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    up = jax.jit(lambda s, *a: S.selective_update(s, *a, interpret=False),
                 donate_argnums=0).lower(
        f32(b, n, e), f32(b, e), f32(b, e), f32(n, e), f32(b, n), f32(b, n),
        jax.ShapeDtypeStruct((b,), jnp.bool_, sharding=one_chip)).compile()
    assert _calls(up.as_text(), "selective_update") == 1
    assert up.memory_analysis().alias_size_in_bytes == b * n * e * 4
    assert up.memory_analysis().temp_size_in_bytes < 2 ** 20
    scan = jax.jit(lambda *a: S.selective_scan(*a, interpret=False)).lower(
        f32(1, length, e), f32(1, length, e), f32(n, e), f32(1, length, n),
        f32(1, length, n), f32(1, n, e)).compile()
    assert _calls(scan.as_text(), "selective_scan") == 1
    assert scan.memory_analysis().temp_size_in_bytes < length * e * 4


# The grouped expert products of one chunk, their layouts sized for every pick
# (top-8, row blocks of 128): (chunk tokens, held experts, hidden, expert width)
# of K-EXAONE's 2,048-token chunks (16 of 128 experts) and of Sarvam's 1,024
# (32 of 128), and the padded rows each layout holds.
GMM_CHUNKS = {"k-exaone": ((2048, 16, 6144, 2048), 18432),
              "sarvam": ((1024, 32, 4096, 2048), 12288)}


@pytest.mark.parametrize("cell", sorted(GMM_CHUNKS))
def test_the_parked_grouped_products_compile_for_v5e(one_chip, cell):
    """``moe_gmm_act`` (silu(x W_gate) * x W_up) and ``moe_gmm`` (W_down) with
    their dead steps parked — the ``park`` array scalar-prefetched and read
    by the row and weight index maps — at the chunk shapes of the two cells
    whose experts take them: Mosaic takes both."""
    from k8s_distributed_deeplearning_tpu.ops import pallas_gmm
    (tokens, held, d, f), m_pad = GMM_CHUNKS[cell]
    assert pallas_gmm.padded_rows(tokens * 8, held, 128) == m_pad
    sds = lambda *shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt,
                                                               sharding=one_chip)

    def experts(x, counts, w_gate, w_up, w_down):
        layout = pallas_gmm.grouped_layout(counts, tokens * 8, block_m=128)
        hidden = pallas_gmm.gmm_act(x, (w_gate, w_up), layout, "swiglu",
                                    interpret=False)
        return pallas_gmm.gmm_forward(hidden, w_down, layout, interpret=False)
    text = jax.jit(experts).lower(
        sds(m_pad, d), sds(held, dt=jnp.int32), sds(held, d, f), sds(held, d, f),
        sds(held, f, d)).compile().as_text()
    assert _calls(text, "moe_gmm_act") == 1 and _calls(text, "moe_gmm") == 1


def test_the_parked_training_products_compile_for_v5e(one_chip):
    """Training's grouped products under ``jax.grad``: the parked forward and
    ``dlhs`` (``moe_gmm`` on the transposed weights) beside ``tgmm``, in bf16
    at 128-row blocks with an empty expert: Mosaic takes all three."""
    from k8s_distributed_deeplearning_tpu.ops import pallas_gmm
    sds = lambda *shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt,
                                                               sharding=one_chip)

    def grads(lhs, rhs, counts):
        layout = pallas_gmm.grouped_layout(counts, 1536, block_m=128)
        return jax.grad(lambda l, r: jnp.sum(pallas_gmm.gmm(
            l, r, layout, interpret=False).astype(jnp.float32) ** 2),
            argnums=(0, 1))(lhs, rhs)
    m_pad = pallas_gmm.padded_rows(1536, 4, 128)
    text = jax.jit(grads).lower(sds(m_pad, 256), sds(4, 256, 512),
                                sds(4, dt=jnp.int32)).compile().as_text()
    # under jax.grad the kernels' names carry the transform: jvp_moe_gmm_
    # (the forward), transpose_jvp_moe_gmm__ (dlhs), transpose_jvp___ (tgmm)
    kernels = re.findall(r"%(\w+?)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"",
                         text)
    assert len(kernels) == 3 and sum("moe_gmm" in k for k in kernels) == 2, kernels


def _calls(text, kernel):
    """Custom calls of Pallas kernel *kernel* in compiled module *text*
    (the name alone would also match the kernels' source paths)."""
    return len(re.findall(rf"%{kernel}(\.\d+)? = [^\n]*custom-call", text))


def _jamba_cell(one_chip):
    """(model, params, cache, engine options, sds) of
    `jamba2-3b.chat-backlog-wide` as shapes placed on a described chip: 28
    unrolled layers at the published widths, the page pool of the two
    attention layers and the state arena of the 26 Mamba-1 layers."""
    import flax.linen as nn

    from benchmarks.harness import family_jamba as fam
    from benchmarks.harness import manifest as M
    from benchmarks.harness import reference_jamba as ref
    from k8s_distributed_deeplearning_tpu.models.transformer import PatternLM
    cell = M.Cell(M.load_manifest(), "jamba2-3b.chat-backlog-wide")
    cfg, eng = cell.config, cell.options["engine"]
    slots, pt, pages = eng["num_slots"], eng["page_tokens"], eng["kv_pool_pages"] + 1
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    model = PatternLM(*fam.program_config(cfg, eng["max_seq_len"]))
    params = jax.tree.map(
        lambda a: sds(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda: nn.meta.unbox(model.init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])))
    lanes = cfg["num_key_value_heads"] * cfg["head_dim"]
    e, n, _, k = ref.mamba_sizes(cfg)
    cache = {"transformer": {f"block_{i}": {"attn": (
        {"cached_key": sds((pages, pt, lanes), jnp.bfloat16),
         "cached_value": sds((pages, pt, lanes), jnp.bfloat16)}
        if ref.is_attention(cfg, i) else
        {"conv_state": sds((slots, k - 1, e), jnp.bfloat16),
         "ssm_state": sds((slots, n, e), jnp.float32)})}
        for i in range(cfg["num_hidden_layers"])}}
    return model, params, cache, eng, sds


def test_the_jamba_cells_decode_program_compiles_and_fits_one_chip(one_chip, monkeypatch):
    """`jamba2-3b.chat-backlog-wide`'s decode program whole, from shapes alone:
    128 slots through 28 unrolled layers — `selective_update` in the 26
    Mamba-1 layers with the arena in place (no select over and no copy of a
    layer's 42 MB of state), the paged kernel at 20 query heads a KV head in
    the two attention layers — and what it holds (6.06 GB of weights, 1.19 GB
    of state, 0.54 GB of pages) beside its temporaries inside one v5e."""
    from k8s_distributed_deeplearning_tpu.models import transformer as T
    from k8s_distributed_deeplearning_tpu.ops import pallas_selective_scan as S
    from k8s_distributed_deeplearning_tpu.serve import engine as E
    for mod in (pallas_paged_attn, S, T):
        monkeypatch.setattr(mod, "on_tpu", lambda: True)         # compile, not interpret
    model, params, cache, eng, sds = _jamba_cell(one_chip)
    slots, pt = eng["num_slots"], eng["page_tokens"]
    i32, f32 = (lambda *s: sds(s, jnp.int32)), (lambda *s: sds(s, jnp.float32))
    compiled = E._decode_program.lower(
        model, params, cache, i32(slots), i32(slots),
        i32(slots, eng["max_seq_len"] // pt), f32(slots), i32(slots), f32(slots),
        sds((slots, 2), jnp.uint32)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert _calls(text, "selective_update") == 26 and _calls(text, "paged_attn") == 2
    assert _calls(text, "selective_scan") == 0
    assert [l for l in text.splitlines()
            if re.search(r"= f32\[128,16,5120\][^ ]* (select|copy)\(", l)] == []
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 7.7e9 < mem.argument_size_in_bytes < 7.9e9 and held < 9e9
    assert mem.alias_size_in_bytes > 1.7e9                      # arena and pool in place


def test_the_jamba_cells_chunk_program_scans_through_the_kernel(one_chip, monkeypatch):
    """The same cell's intermediate CHUNK program, 512 tokens of one slot: 26
    selective scans through the kernel — no ``[512, 16, 5120]`` float32
    intermediates — and the two attention layers through the paged kernel in
    query blocks of 16."""
    from k8s_distributed_deeplearning_tpu.models import transformer as T
    from k8s_distributed_deeplearning_tpu.ops import pallas_selective_scan as S
    from k8s_distributed_deeplearning_tpu.serve import engine as E
    for mod in (pallas_paged_attn, S, T):
        monkeypatch.setattr(mod, "on_tpu", lambda: True)         # compile, not interpret
    model, params, cache, eng, sds = _jamba_cell(one_chip)
    c, blocks = eng["prefill_chunk_tokens"], eng["max_seq_len"] // eng["page_tokens"]
    text = E._chunk_program.lower(
        model, params, cache, sds((1, c), jnp.int32), sds((1, blocks), jnp.int32),
        sds((), jnp.int32), sds((), jnp.int32)).compile().as_text()
    assert _calls(text, "selective_scan") == 26 and _calls(text, "paged_attn") >= 1
    assert "f32[1,512,16,5120]" not in text and "f32[512,16,5120]" not in text


def _window_moe_cell(one_chip):
    """(model, params, cache, engine options, sds) of
    `k-exaone-ep8-d5.docs-backlog-wide` as shapes placed on a described chip:
    5 unrolled layers at the published widths (4 sliding, 1 global), the page
    pool of the global layer and the rings of the four window layers."""
    import flax.linen as nn

    from benchmarks.harness import family_window_moe as fam
    from benchmarks.harness import manifest as M
    from benchmarks.harness import reference_window_moe as ref
    from k8s_distributed_deeplearning_tpu.models.transformer import PatternLM
    cell = M.Cell(M.load_manifest(), "k-exaone-ep8-d5.docs-backlog-wide")
    cfg, eng = cell.config, cell.options["engine"]
    slots, pt, pages = eng["num_slots"], eng["page_tokens"], eng["kv_pool_pages"] + 1
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    model = PatternLM(*fam.program_config(cfg, eng["max_seq_len"]))
    params = jax.tree.map(
        lambda a: sds(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda: nn.meta.unbox(model.init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])))
    lanes = cfg["num_key_value_heads"] * cfg["head_dim"]
    w = cfg["sliding_window"]
    cache = {"transformer": {f"block_{i}": {"attn": (
        {"window_key": sds((slots, w, lanes), jnp.bfloat16),
         "window_value": sds((slots, w, lanes), jnp.bfloat16)}
        if mixer == "sliding_attention" else
        {"cached_key": sds((pages, pt, lanes), jnp.bfloat16),
         "cached_value": sds((pages, pt, lanes), jnp.bfloat16)})}
        for i, (mixer, _) in enumerate(ref.layer_kinds(cfg))}}
    return model, params, cache, eng, sds


def test_the_window_moe_cells_decode_program_compiles_and_fits_one_chip(one_chip, monkeypatch):
    """`k-exaone-ep8-d5.docs-backlog-wide`'s decode program whole, from shapes
    alone: 64 slots through 5 unrolled layers — each window layer's token
    scattered into its ring in place (no select over and no copy of a layer's
    16 MB ring), the paged kernel at 8 query heads a KV head in the global
    layer — and what it holds (7.42 GB of weights, 3.76 GB of pages, 134 MB
    of rings) beside its temporaries inside one v5e."""
    from k8s_distributed_deeplearning_tpu.models import transformer as T
    from k8s_distributed_deeplearning_tpu.ops import pallas_gmm
    from k8s_distributed_deeplearning_tpu.serve import engine as E
    for mod in (pallas_paged_attn, pallas_gmm, T):
        monkeypatch.setattr(mod, "on_tpu", lambda: True)         # compile, not interpret
    model, params, cache, eng, sds = _window_moe_cell(one_chip)
    slots, pt = eng["num_slots"], eng["page_tokens"]
    i32, f32 = (lambda *s: sds(s, jnp.int32)), (lambda *s: sds(s, jnp.float32))
    compiled = E._decode_program.lower(
        model, params, cache, i32(slots), i32(slots),
        i32(slots, eng["max_seq_len"] // pt), f32(slots), i32(slots), f32(slots),
        sds((slots, 2), jnp.uint32)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert _calls(text, "paged_attn") == 1
    assert [l for l in text.splitlines()
            if re.search(r"= bf16\[64,128,1024\][^ ]* (select|copy)\(", l)] == []
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 11.2e9 < mem.argument_size_in_bytes < 11.4e9 and held < 12.5e9
    assert mem.alias_size_in_bytes > 3.8e9                      # rings and pool in place


def test_the_window_moe_cells_chunk_program_attends_through_the_window_kernel(
        one_chip, monkeypatch):
    """The same cell's intermediate CHUNK program, 2,048 tokens of one slot:
    the four window layers through ``window_attn`` (blocks of 128 queries
    against their 256 keys in VMEM: no score tensor of the band in HBM, and
    no ``[.., 2048, 2176]`` one), the global layer through the paged kernel,
    the experts through the grouped kernel; it fits beside the cache."""
    from k8s_distributed_deeplearning_tpu.models import transformer as T
    from k8s_distributed_deeplearning_tpu.ops import pallas_gmm, pallas_window_attn
    from k8s_distributed_deeplearning_tpu.serve import engine as E
    for mod in (pallas_paged_attn, pallas_gmm, pallas_window_attn, T):
        monkeypatch.setattr(mod, "on_tpu", lambda: True)         # compile, not interpret
    model, params, cache, eng, sds = _window_moe_cell(one_chip)
    c, blocks = eng["prefill_chunk_tokens"], eng["max_seq_len"] // eng["page_tokens"]
    compiled = E._chunk_program.lower(
        model, params, cache, sds((1, c), jnp.int32), sds((1, blocks), jnp.int32),
        sds((), jnp.int32), sds((), jnp.int32)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert _calls(text, "paged_attn") >= 1 and _calls(text, "moe_gmm_act") >= 3
    assert _calls(text, "window_attn") == 4
    assert "2176]" not in text and ",128,256]" not in text
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 13e9

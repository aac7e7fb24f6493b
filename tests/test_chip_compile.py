"""The main paths' Pallas kernels, compiled for a described TPU v5e.

The sandbox has no chip, but the TPU's compiler is installed and compiles
for a chip that is described and not attached (on-chip-measurement guide
§2.3). Interpret mode accepts block shapes, scratch shapes and index types
that Mosaic refuses — every kernel here passed its interpret-mode tests
while two of them could not be lowered for the chip — so these compiles
guard what the CPU tests cannot see, at no chip time: real widths, through
the public entry points, with the framework's global x64 ON as callers
have it. Nothing runs; a compile that passes is not a chip run
(`python chip_smoke.py` is).

The dispatch gate `_on_tpu()` still sees the CPU, so each test steers it
with monkeypatch — in the test, not through an option of the program.
"""
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp

import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle  # noqa: F401  (turns the global x64 on)
from paddle_tpu.ops import fused_optimizer as fo
from paddle_tpu.ops import pallas as pk

KERNEL = "tpu_custom_call"
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    """Sharding on one device of a described v5e 2x2; skip where the
    installation cannot describe it."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / no such topology here
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _chip_compile_env(monkeypatch):
    """Kernels dispatch as on a TPU; the persistent compilation cache stays
    off (a described-device compile is written to it but cannot be read back
    without a chip: the next run would warn and compile again)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    assert jax.config.jax_enable_x64, "the framework's global x64 must be on"
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _kernels(fn, *avals, **kw_avals):
    """Compile fn for the described chip; count its Pallas kernels."""
    return jax.jit(fn).lower(*avals, **kw_avals).compile().as_text().count(KERNEL)


def _aval(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("shape_q, shape_kv, causal, dropout", [
    # ERNIE seq 4096, heads 6x128, attention dropout 0.1: the 1024-wide
    # tiles under the 40 MB _VMEM_LIMIT
    ((2, 4096, 6, 128), (2, 4096, 6, 128), False, 0.1),
    # decoder widths: GQA 32q/8kv at head 128, causal
    ((1, 4096, 32, 128), (1, 4096, 8, 128), True, 0.0),
    # ERNIE seq 128, heads 12x64 (explicit call; auto-dispatch gates S>=512)
    ((64, 128, 12, 64), (64, 128, 12, 64), False, 0.0),
], ids=["s4096_6x128_dropout", "gqa_32q8kv_causal", "s128_12x64"])
def test_flash_fwd_bwd_compiles(one_chip, shape_q, shape_kv, causal, dropout):
    def grads(q, k, v):
        def loss(q, k, v):
            out = pk.flash_attention_bshd(
                q, k, v, causal=causal, dropout_p=dropout, dropout_seed=7)
            return out.astype(jnp.float32).sum()

        return jax.grad(loss, (0, 1, 2))(q, k, v)

    q = _aval(one_chip, shape_q, BF16)
    kv = _aval(one_chip, shape_kv, BF16)
    assert _kernels(grads, q, kv, kv) == 3  # fwd, dq, dkdv


def _paged_kernels(one_chip, pool_dtype, q_dtype, q_len, B, N, M):
    """Hidden-4096 widths (32q/8kv, head 128), block 16, pool [N, Hkv, bs, D]
    — through flash_decode_paged / flash_decode_paged_multi."""
    H, HKV, D, BS = 32, 8, 128, 16
    pages = _aval(one_chip, (N, HKV, BS, D), pool_dtype)
    bt = _aval(one_chip, (B, M), jnp.int32)
    scales = {}
    if pool_dtype == jnp.int8:
        sc = _aval(one_chip, (N, HKV, BS), jnp.float32)
        scales = {"k_scales": sc, "v_scales": sc}
    if q_len == 1:
        fn = pk.flash_decode_paged
        q = _aval(one_chip, (B, H, D), q_dtype)
        where = _aval(one_chip, (B,), jnp.int32)  # seq_lens
    else:
        fn = pk.flash_decode_paged_multi
        q = _aval(one_chip, (B, q_len, H, D), q_dtype)
        where = _aval(one_chip, (B, q_len), jnp.int32)  # q_positions
    return _kernels(fn, q, pages, pages, bt, where, **scales)


@pytest.mark.parametrize("q_len", [1, 4], ids=["decode", "extend_q4"])
@pytest.mark.parametrize("pool_dtype, q_dtype", [
    (BF16, BF16), (jnp.float32, jnp.float32), (jnp.int8, BF16),
], ids=["bf16", "f32", "int8"])
def test_paged_attention_compiles(one_chip, pool_dtype, q_dtype, q_len):
    assert _paged_kernels(one_chip, pool_dtype, q_dtype, q_len, B=8, N=256, M=16) == 1


@pytest.mark.parametrize("q_len", [1, 4], ids=["decode", "extend_q4"])
@pytest.mark.parametrize("B, M", [(32, 64), (1, 256), (8, 20)],
                         ids=["chat_open_b32_m64", "doc_single_b1_m256", "ragged_m20"])
def test_paged_attention_compiles_at_the_cells_shapes(one_chip, B, M, q_len):
    """The benchmark's two serving cells (the 32-row bucket over a 64-page
    table, one row over 256 pages; the pool of 4097 pages), and a table the
    kernel's block of 8 pages does not divide."""
    assert _paged_kernels(one_chip, BF16, BF16, q_len, B=B, N=4097, M=M) == 1


@pytest.mark.parametrize("pool_dtype", [BF16, jnp.int8], ids=["bf16", "int8"])
def test_paged_attention_compiles_for_a_prompts_chunk(one_chip, pool_dtype):
    """The chunk row of the engine's chunk program: ONE row of 128 queries
    (512 query rows a kv head's tile, its frontier from an iota) over the
    chat cell's table."""
    assert _paged_kernels(one_chip, pool_dtype, BF16, 128, B=1, N=4097, M=64) == 1


def test_decode_kernel_body_at_one_query_is_what_it_was():
    """PR 30 gave the kernel's mask a second form for rows of many
    consecutive queries. Every decode step of every serving cell runs the
    kernel at ONE query a row, and what it traces to there is, letter for
    letter, what PR 29's tree traced to (the digest below is of that tree's
    text; a jaxpr carries no source locations). A PR that changes the
    one-query kernel on purpose replaces the digest, and measures decode."""
    import hashlib

    B, H, HKV, D, BS, N, M = 32, 32, 8, 128, 16, 4097, 64
    pages = jax.ShapeDtypeStruct((N, HKV, BS, D), BF16)
    with jax.enable_x64(False):
        text = str(jax.make_jaxpr(lambda *a: pk._paged_decode_impl(*a, None))(
            jax.ShapeDtypeStruct((B, H, D), BF16), pages, pages,
            jax.ShapeDtypeStruct((B, M), jnp.int32), jax.ShapeDtypeStruct((B,), jnp.int32)))
    assert "paged_attn" in text
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "475a45a666ca3cf3bdbb192b375084271425f70a55e7d36f6c61ed0e2a412752")


def test_paged_attention_compiles_at_the_hybrid_cells_shapes(one_chip):
    """The hybrid decoder's one attention layer: 32 query heads over 2 kv
    heads (16 queries a kv head against the dense decoder's 4), the 128-row
    bucket over a 64-page table, the pool of 8193 pages."""
    H, HKV, D, BS, B, M, N = 32, 2, 128, 16, 128, 64, 8193
    pages = _aval(one_chip, (N, HKV, BS, D), BF16)
    assert _kernels(pk.flash_decode_paged, _aval(one_chip, (B, H, D), BF16), pages, pages,
                    _aval(one_chip, (B, M), jnp.int32), _aval(one_chip, (B,), jnp.int32)) == 1


def _pool_copies(compiled, pool_aval):
    """`copy` instructions of the optimized program whose result has the
    pool's shape: the change of layout XLA puts around a scatter it cannot
    make in place, a layer's whole pool read and written each."""
    dt = {"bfloat16": "bf16", "int8": "s8", "float32": "f32"}[str(pool_aval.dtype)]
    shape = re.escape(f"{dt}[{','.join(map(str, pool_aval.shape))}]")
    return re.findall(r"= " + shape + r"\S* copy\(", compiled.as_text())


@pytest.mark.parametrize("n, hkv, b, s, m, how, pool_dtype", [
    (4097, 8, 32, 1, 64, "positions", BF16), (4097, 8, 1, 512, 64, "positions", BF16),
    (4097, 8, 4, 4, 64, "masked", BF16), (8193, 2, 128, 1, 64, "positions", BF16),
    (4097, 8, 32, 1, 64, "positions", jnp.int8),
    (4097, 8, 1, 512, 64, "prefill", BF16), (4097, 8, 1, 4096, 256, "prefill", BF16),
    (8193, 2, 1, 256, 64, "prefill", BF16), (4097, 8, 1, 512, 64, "prefill", jnp.int8),
], ids=["decode_b32", "extend_1x512", "extend_4x4_masked", "hybrid_decode_b128", "int8_decode_b32",
        "prefill_s512", "prefill_s4096", "hybrid_prefill_s256", "int8_prefill_s512"])
def test_kv_write_is_in_place(one_chip, n, hkv, b, s, m, how, pool_dtype):
    """`PagedCacheView.write` at the serving cells' shapes (the dense
    decoder's pool, the hybrid's one attention layer, the int8 pool's pages),
    the pool donated: positioned rows through the scatter by page, head and
    slot, a prefill (positions None) by whole pages; either updates the pool
    where it lies."""
    from paddle_tpu.inference.kv_cache import PagedCacheView

    bs, d = 16, 128
    pool = _aval(one_chip, (n, hkv, bs, d), pool_dtype)
    new = _aval(one_chip, (b, s, hkv, d), BF16)
    state = {"k": [pool], "v": [pool]}
    if pool_dtype == jnp.int8:
        state["k_scale"] = state["v_scale"] = [_aval(one_chip, (n, hkv, bs), jnp.float32)]

    def fn(state, bt, k_new, v_new, positions=None, mask=None):
        view = PagedCacheView.from_state(state, bt, jnp.zeros((b,), jnp.int32), bs, write_mask=mask)
        view.write(0, k_new, v_new, positions)
        return PagedCacheView.state_of(view)

    extra = {"prefill": (), "positions": (_aval(one_chip, (b, s), jnp.int32),),
             "masked": (_aval(one_chip, (b, s), jnp.int32), _aval(one_chip, (b, s), jnp.bool_))}[how]
    compiled = jax.jit(fn, donate_argnums=0).lower(
        state, _aval(one_chip, (b, m), jnp.int32), new, new, *extra).compile()
    assert _pool_copies(compiled, pool) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 24 << 20   # a copy of the bf16 pool is 134 MB


def _described_engine(one_chip, monkeypatch, make_model, **engine_kw):
    """An InferenceEngine over a model whose leaves are bf16 shapes (no weight
    and no page exists: model and engine are built under `jax.eval_shape`),
    its programs lowered for the described chip with the state donated."""
    from paddle_tpu.inference.engine import InferenceEngine

    box = {}

    def construct():
        model = make_model()
        model.eval()
        for t in model.state_dict().values():
            t._value = jax.ShapeDtypeStruct(t.shape, BF16)
        box["engine"] = InferenceEngine(model, **engine_kw)
        return 0

    jax.eval_shape(construct)
    paddle.seed(0)  # the constructor's draws left a traced key behind
    engine = box["engine"]
    jit = engine._jit

    class ForTheChip:
        """The engine lowers avals that name no device: name the described one."""
        def __init__(self, jitted):
            self.jitted = jitted

        def lower(self, *avals):
            return self.jitted.lower(*jax.tree.map(lambda a: _aval(one_chip, a.shape, a.dtype), avals))

    monkeypatch.setattr(engine, "_donate", True)
    monkeypatch.setattr(engine, "_jit", lambda fn, n_args: ForTheChip(jit(fn, n_args)))
    return engine


def _kernels_of(text):
    """Instruction names of the program's Pallas kernels, version suffix dropped."""
    return [re.sub(r"\.\d+$", "", k) for k in
            re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target=\"" + KERNEL + "\"", text)]


def _assert_ids(compiled, program, engine):
    """A decode or a chunk program hands back, beside the logits, the token it
    chose a row: int32, the largest bucket's rows and a chunk's last token
    (one length whatever the bucket, so any step can take any step's ids)."""
    if program not in ("decode", "chunk"):
        return
    logits, ids = compiled.out_info[0][:2]
    assert ids.shape == (engine.decode_batch_buckets[-1] + 1,) and ids.dtype == jnp.int32
    assert len(logits.shape) == 2 and logits.shape[1] == engine.vocab_size


@pytest.mark.parametrize("program, size, scatters", [
    ("decode", 32, 4), ("prefill", 512, 4), ("extend", (4, 4), 4),
    ("chunk", 32, 8),   # the rows' positioned write and the chunk's whole pages, K and V, a layer
], ids=["decode_b32", "prefill_s512", "extend_4x4", "chunk_b32_c128"])
def test_engine_program_holds_no_copy_of_the_pool(one_chip, monkeypatch, program, size, scatters):
    """The whole program: the engine's 32-row decode, 512-token prefill,
    (4, 4) extend and chunk step (32 rows and 128 prompt tokens) of a 2-layer
    decoder at Mistral widths (no weight and no page exists: model and engine
    are built under `jax.eval_shape`), state donated as on the chip. The chunk
    step's two paged-attention calls a layer keep the kernel's name."""
    from paddle_tpu.models.llama import LlamaForCausalLM

    engine = _described_engine(
        one_chip, monkeypatch,
        lambda: LlamaForCausalLM(vocab_size=32000, hidden_size=4096, num_hidden_layers=2,
                                 num_attention_heads=32, num_key_value_heads=8,
                                 intermediate_size=14336, rms_norm_eps=1e-5),
        max_seq_len=1024, block_size=16, num_blocks=4097, max_batch=32)
    compile_ = getattr(engine, "_compile_" + program)
    compiled = compile_(*size) if isinstance(size, tuple) else compile_(size)
    text = compiled.as_text()
    assert text.count(" scatter(") == scatters   # K and V written, a layer
    if program == "chunk":
        assert engine.chunk_width == 128
        assert _kernels_of(text) == ["paged_attn"] * 4
    if program == "decode":
        assert _kernels_of(text) == ["paged_attn"] * 2   # one query a row still goes through the kernel
    # the `where` on the token operand (a row's token may be the last step's, on the device) and the
    # ids beside the logits leave the donated pool where it lies
    assert _pool_copies(compiled, engine._state_avals()["k"][0]) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20   # a copy of the pool is 134 MB
    _assert_ids(compiled, program, engine)


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_single_stream_programs_choose_the_token_on_the_device(one_chip, monkeypatch, program):
    """The document cell's engine (one slot, a 4096-token table, the pool of
    4097 pages) over a 2-layer decoder at Mistral widths: its one-row decode
    and the lone slot's chunk program return the ids `[2]` (the row's and a
    chunk's last), hold no `copy` of the pool, and run the paged kernel."""
    from paddle_tpu.models.llama import LlamaForCausalLM

    engine = _described_engine(
        one_chip, monkeypatch,
        lambda: LlamaForCausalLM(vocab_size=32000, hidden_size=4096, num_hidden_layers=2,
                                 num_attention_heads=32, num_key_value_heads=8,
                                 intermediate_size=14336, rms_norm_eps=1e-5),
        max_seq_len=4096, block_size=16, num_blocks=4097, max_batch=1,
        prefill_buckets=(1024, 2048, 4096), decode_batch_buckets=(1,))
    compiled = getattr(engine, "_compile_" + program)(1)
    assert _kernels_of(compiled.as_text()) == ["paged_attn"] * (2 if program == "decode" else 4)
    assert _pool_copies(compiled, engine._state_avals()["k"][0]) == []
    _assert_ids(compiled, program, engine)
    assert compiled.out_info[0][1].shape == (2,)


@pytest.mark.parametrize("program, size, kernels", [
    ("decode", 16, {"mla_paged_attn": 2, "moe_gmm": 2}),
    ("chunk", 16, {"mla_paged_attn": 4, "moe_gmm": 2}),   # rows and chunk, a layer
    ("prefill", 1024, {"flash_fwd": 2, "moe_gmm": 2}),     # the expanded path: no paged kernel
], ids=["decode_b16", "chunk_b16_c128", "prefill_s1024"])
def test_latent_engine_programs_compile_at_published_widths(one_chip, monkeypatch, program, size, kernels):
    """The long-document cell's engine over a decoder of openPangu-Ultra-MoE's
    published widths, one leading dense and one sparse layer (16 of 256
    experts held): the 16-row decode, the chunk step (16 rows and 128 prompt
    tokens) and the 1024 prefill bucket. The latent pool is `[8705, 16, 640]`
    (576 in whole lane tiles) and no program holds a `copy` of that shape:
    the write lands in place and the kernel reads the pool where it lies."""
    from paddle_tpu.models.pangu_ultra_moe import PanguUltraMoEForCausalLM

    engine = _described_engine(
        one_chip, monkeypatch,
        lambda: PanguUltraMoEForCausalLM(
            vocab_size=19200, hidden_size=7680, num_hidden_layers=2, first_k_dense_replace=1,
            num_attention_heads=128, q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128, intermediate_size=18432, moe_intermediate_size=2048,
            n_routed_experts=256, experts_held=[0, 16], num_experts_per_tok=8),
        max_seq_len=8704, block_size=16, num_blocks=8705, max_batch=16,
        prefill_buckets=(1024, 2048, 4096, 8192), decode_batch_buckets=(1, 2, 4, 8, 16))
    pool = engine._state_avals()
    assert pool["k"][0].shape == (8705, 16, 640) and pool["v"] == [] and engine.chunk_width == 128
    compiled = getattr(engine, "_compile_" + program)(size)
    text = compiled.as_text()
    names = _kernels_of(text)
    assert {k: names.count(k) for k in set(names)} == kernels
    assert _pool_copies(compiled, pool["k"][0]) == []
    _assert_ids(compiled, program, engine)
    if program != "prefill":  # a prefill's temporaries are its activations' (0.3 GB at 1024 tokens)
        assert compiled.memory_analysis().temp_size_in_bytes < 96 << 20   # a copy of the pool is 178 MB


@pytest.mark.parametrize("program, size, kernels", [
    ("decode", 8, {"dsa_index": 2, "mla_sparse_paged_attn": 2, "mla_paged_attn": 2, "moe_gmm": 2}),
    ("chunk", 8, {"dsa_index": 4, "mla_sparse_paged_attn": 4, "mla_paged_attn": 4, "moe_gmm": 2}),  # rows and chunk
    ("prefill", 4096, {"dsa_index": 2, "mla_sparse_paged_attn": 2, "mla_paged_attn": 2, "moe_gmm": 2}),
], ids=["decode_b8", "chunk_b8_c128", "prefill_s4096"])
def test_sparse_latent_engine_programs_compile_at_published_widths(one_chip, monkeypatch, program, size, kernels):
    """The long-context cell's engine over a decoder of DeepSeek-V3.2's
    published widths, one leading dense and one sparse layer (16 of 256
    experts held): the 8-row decode, the chunk step (8 rows and 128 prompt
    tokens) and the 4096 prefill bucket, which attends through the cache in
    tiles of 128 queries. A layer keeps two arrays under one page id, the
    latent entries `[8705, 16, 640]` and the index keys `[8705, 16, 128]`; no
    program holds a `copy` of either shape (the three write paths land in
    place, the kernels and the gather of chosen entries read the pool where
    it lies), and each tile carries both branches: the selector's two kernels
    and the dense kernel for a tile below `index_topk`."""
    from paddle_tpu.models.deepseek_v32 import DeepseekV32ForCausalLM

    engine = _described_engine(
        one_chip, monkeypatch,
        lambda: DeepseekV32ForCausalLM(
            vocab_size=16160, hidden_size=7168, num_hidden_layers=2, first_k_dense_replace=1,
            num_attention_heads=128, q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128, index_n_heads=64, index_head_dim=128, index_topk=2048,
            intermediate_size=18432, moe_intermediate_size=2048, n_routed_experts=256, experts_held=[0, 16],
            num_experts_per_tok=8, n_group=8, topk_group=4,
            rope_scaling={"factor": 40, "original_max_position_embeddings": 4096, "beta_fast": 32,
                          "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}),
        max_seq_len=17408, block_size=16, num_blocks=8705, max_batch=8,
        prefill_buckets=(4096, 8192), decode_batch_buckets=(1, 2, 4, 8))
    pool = engine._state_avals()
    assert pool["k"][0].shape == (8705, 16, 640) and pool["index"][0].shape == (8705, 16, 128)
    assert pool["v"] == [] and engine.chunk_width == 128 and engine.index_topk == 2048
    compiled = getattr(engine, "_compile_" + program)(size)
    text = compiled.as_text()
    names = _kernels_of(text)
    assert {k: names.count(k) for k in set(names)} == kernels
    for aval in (pool["k"][0], pool["index"][0]):
        assert _pool_copies(compiled, aval) == []
    _assert_ids(compiled, program, engine)
    # a tile's gathered entries are 128 x 2048 x 640 bf16 = 335 MB, held twice; a copy of the latent pool is 178 MB more
    limit = {"decode": 96 << 20, "chunk": 1024 << 20, "prefill": 2048 << 20}[program]
    assert compiled.memory_analysis().temp_size_in_bytes < limit


def test_selector_kernels_compile_for_rows_chunk_and_extend(one_chip):
    """The selector's two kernels alone at the cell's shapes: 8 rows of one
    query, one row of a 128-query chunk and a (4, 4) extend, over the
    `[8705, 16, 128]` index keys and the `[8705, 16, 640]` latent pool and a
    1088-page table; and the exact selection between them (a sort of the
    table's 17,408 positions a query)."""
    keys, lat = _aval(one_chip, (8705, 16, 128), BF16), _aval(one_chip, (8705, 16, 640), BF16)

    def attend(q, lat, rows, counts):
        return pk.mla_sparse_attention(q, lat, rows, counts, 512, 192 ** -0.5)

    for b, q_len in ((8, 1), (1, 128), (4, 4)):
        table = _aval(one_chip, (b, 1088), jnp.int32)
        assert _kernel_names(pk.dsa_index_scores, _aval(one_chip, (b, q_len, 64, 128), BF16),
                             _aval(one_chip, (b, q_len, 64), jnp.float32), keys, table,
                             _aval(one_chip, (b, q_len), jnp.int32)) == ["dsa_index"]
        assert _kernel_names(attend, _aval(one_chip, (b, q_len, 128, 576), BF16), lat,
                             _aval(one_chip, (b, q_len, 2048), jnp.int32),
                             _aval(one_chip, (b, q_len), jnp.int32)) == ["mla_sparse_paged_attn"]
    chosen = jax.jit(lambda s, c, f: pk.dsa_select(s, 2048, carry=c, frontier=f)).lower(
        _aval(one_chip, (1, 128, 17408), jnp.float32), _aval(one_chip, (1, 17408), jnp.int32),
        _aval(one_chip, (), jnp.int32)).compile()
    assert "s32[1,128,2048]" in chosen.as_text() and chosen.as_text().count(" sort(") == 3  # a quarter, a half, all


def test_hybrid_chunk_program_updates_the_state_in_place_at_published_widths(one_chip, monkeypatch):
    """The reasoning cell's engine over a hybrid of Nemotron-3-Super's
    published widths (two state-space layers, one attention layer, two expert
    layers of the 128 held experts; 128 slots, the pool of 8193 pages): its
    ONE chunk program, 128 decode rows and 128 prompt tokens of one more
    sequence. The rows' whole-array state update and the chunk's one-slot
    update both land on the donated arrays (no `copy` of a layer's state,
    541 MB, nor of the pool), the chunk's recurrence is the block form (no
    loop carries a state), and the kernels keep their names."""
    from paddle_tpu.models.nemotron_h import NemotronHForCausalLM

    engine = _described_engine(
        one_chip, monkeypatch,
        lambda: NemotronHForCausalLM(
            vocab_size=32768, hidden_size=4096, hybrid_override_pattern="MEM*E", num_attention_heads=32,
            num_key_value_heads=2, head_dim=128, mamba_num_heads=128, mamba_head_dim=64, ssm_state_size=128,
            n_groups=8, conv_kernel=4, n_routed_experts=512, experts_held=[0, 128], num_experts_per_tok=22,
            moe_latent_size=1024, moe_intermediate_size=2688, moe_shared_expert_intermediate_size=5376),
        max_seq_len=1024, block_size=16, num_blocks=8193, max_batch=128,
        prefill_buckets=(16, 32, 64, 128, 256), decode_batch_buckets=(1, 2, 4, 8, 16, 32, 64, 128))
    state = engine._state_avals()
    assert state["ssm"][0].shape == (129, 128, 64, 128) and engine.chunk_width == 128
    compiled = engine._compile_chunk(128)
    text = compiled.as_text()
    names = _kernels_of(text)
    assert {k: names.count(k) for k in set(names)} == {"paged_attn": 2, "moe_gmm": 4}  # rows and chunk; up and down
    for aval in (state["ssm"][0], state["k"][0]):
        assert _pool_copies(compiled, aval) == []
    _assert_ids(compiled, "chunk", engine)
    loops = [line for line in text.splitlines() if " while(" in line]
    assert not [line for line in loops if "64,128]" in line]   # the expert layout's loops carry indices alone
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20   # a copy of a layer's state is 541 MB


def _lowered_digest(engine, program, size):
    """sha256 of a program's lowered text, source locations stripped."""
    import hashlib

    box = []
    jit = engine._jit

    class Lowered:
        def __init__(self, jitted):
            self.jitted = jitted

        def lower(self, *avals):
            box.append(self.jitted.lower(*avals).as_text())
            return self

        def compile(self):
            return None

    engine._jit = lambda fn, n_args: Lowered(jit(fn, n_args))
    try:
        getattr(engine, "_compile_" + program)(*(size if isinstance(size, tuple) else (size,)))
    finally:
        engine._jit = jit
    text = re.sub(r"\s*loc\([^\n]*\)", "", box[0])
    text = "\n".join(line for line in text.splitlines() if not line.startswith("#loc"))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


# PR 35: the decode and chunk programs choose the token (ids beside the logits) and take a row's token
# from the last step's ids (two operands more): their six digests are new; `prefill` and `extend` keep
# theirs, which is the change's scope.
LOWERED = {
    ("llama", "decode", 4): "4e798e053ee0185a",
    ("llama", "prefill", 32): "bad05b9a2f237a4d",
    ("llama", "chunk", 4): "a40c2251fa1ee927",
    ("llama", "extend", (4, 4)): "7d875aa6fdd6bf0f",
    ("pangu", "decode", 4): "658273ec7fa6d58d",
    ("pangu", "prefill", 32): "f04d8608c8a0240d",
    ("pangu", "chunk", 4): "aa65fe8b39e12a14",
    ("pangu", "extend", (4, 4)): "7eac7881ec210019",
    ("hybrid", "decode", 4): "04e5c50de0830a52",
    ("hybrid", "prefill", 32): "4d56eb5cb37d11c8",
    ("hybrid", "chunk", 4): "4d3a54478dc28847",
}


@pytest.mark.parametrize("model, program, size", list(LOWERED), ids=lambda v: str(v).replace(" ", ""))
def test_dense_and_latent_programs_lower_to_what_they_did(monkeypatch, model, program, size):
    """PR 32 gave the engine's chunk program two more operands where the
    model keeps recurrent state, and the hybrid's mixer a by-segment branch.
    The programs of a model WITHOUT such state are, letter for letter but for
    source locations, what PR 31's tree lowered (the digests are of that
    tree's text: tiny models, this host's backend, the paged kernels' jnp
    reference path). A PR that changes one on purpose replaces its digest,
    and measures the cells that run it: PR 35 did so for every decode and
    chunk program (ids beside the logits, a row's token from the last step's
    ids), and for no `prefill` and no `extend`."""
    from paddle_tpu.inference.engine import InferenceEngine

    monkeypatch.setattr(pk, "_on_tpu", lambda: False)
    paddle.seed(0)
    if model == "llama":
        from paddle_tpu.models.llama import llama_tiny

        net = llama_tiny(num_key_value_heads=2)
    elif model == "hybrid":
        from paddle_tpu.models.nemotron_h import NemotronHForCausalLM

        net = NemotronHForCausalLM()
    else:
        from paddle_tpu.models.pangu_ultra_moe import PanguUltraMoEForCausalLM

        net = PanguUltraMoEForCausalLM()
    net.eval()
    engine = InferenceEngine(net, max_seq_len=64, block_size=8, max_batch=4)
    assert _lowered_digest(engine, program, size) == LOWERED[model, program, size]


@pytest.mark.parametrize("k, n, gated, out_dtype", [
    (7680, 2048, True, BF16), (2048, 7680, False, jnp.float32)], ids=["gate_up_silu", "down_f32"])
def test_gated_moe_gmm_compiles_at_the_latent_cells_shapes(one_chip, k, n, gated, out_dtype):
    """The gated expert product at the long-document cell's chunk step: 144
    tokens x 8 choices over 16 experts held, hidden 7680 x expert width 2048,
    an expert's matrix 31.5 MB (tiled by columns: two blocks of 3.9 MB a step
    for the gated product), bf16, under the framework's global x64."""
    assignments, groups = 144 * 8, 16
    rows = pk.moe_padded_rows(assignments, groups)
    tiles = rows // pk.MOE_TILE_M
    w = _aval(one_chip, (groups, k, n), BF16)

    def fn(x_rows, w, tile_group, live, *gate):
        return pk.moe_gmm(x_rows, w, tile_group, live, activation="silu" if gated else None,
                          out_dtype=out_dtype, **({"w_gate": gate[0]} if gated else {}))

    assert _kernel_names(fn, _aval(one_chip, (rows, k), BF16), w, _aval(one_chip, (tiles,), jnp.int32),
                         _aval(one_chip, (1,), jnp.int32), *([w] if gated else [])) == ["moe_gmm"]


def test_mla_paged_attention_compiles_for_rows_chunk_and_extend(one_chip):
    """The latent kernel alone at the cell's shapes: 16 rows of one query,
    one row of a 128-query chunk (8 query tiles of 16 x 128 heads), and a
    (4, 4) extend, over the `[8705, 16, 640]` pool and a 544-page table."""
    pages = _aval(one_chip, (8705, 16, 640), BF16)

    def fn(q, pages, bt, pos):
        return pk.mla_paged_attention(q, pages, bt, pos, 512, 192 ** -0.5)

    for b, q_len in ((16, 1), (1, 128), (4, 4)):
        assert _kernel_names(fn, _aval(one_chip, (b, q_len, 128, 576), BF16), pages,
                             _aval(one_chip, (b, 544), jnp.int32),
                             _aval(one_chip, (b, q_len), jnp.int32)) == ["mla_paged_attn"]


@pytest.mark.parametrize("k, n, activation, out_dtype", [
    (1024, 2688, "relu2", BF16), (2688, 1024, None, jnp.float32)], ids=["up_relu2", "down_f32"])
def test_moe_gmm_compiles_at_the_cells_shapes(one_chip, k, n, activation, out_dtype):
    """The expert layer's grouped matmul at the hybrid cell's decode shapes:
    128 rows x 22 choices (704 of them land on the 128 experts held, on
    average; the padded layout holds the worst case), latent 1024 x expert
    width 2688, bf16, under the framework's global x64."""
    assignments, groups = 128 * 22, 128
    rows = pk.moe_padded_rows(assignments, groups)
    tiles = rows // pk.MOE_TILE_M

    def fn(x_rows, w, tile_group, live):
        return pk.moe_gmm(x_rows, w, tile_group, live, activation=activation, out_dtype=out_dtype)

    assert _kernel_names(fn, _aval(one_chip, (rows, k), BF16), _aval(one_chip, (groups, k, n), BF16),
                         _aval(one_chip, (tiles,), jnp.int32), _aval(one_chip, (1,), jnp.int32)) == ["moe_gmm"]


@pytest.mark.parametrize("m2_dtype", [jnp.float32, BF16], ids=["m2_f32", "m2_bf16"])
def test_fused_adamw_compiles_under_global_x64(one_chip, m2_dtype):
    """Through the public fused_adamw_apply, traced as its callers trace it
    (optimizer/fused_engine.py, static/executor.py): x64 on."""
    n = fo.pad_to_tile(85_000_000)  # one ERNIE-3.0-base-sized f32 bucket

    def fn(p, m, v, g, lr):
        return fo.fused_adamw_apply(
            p, m, v, g, lr=lr, clip_scale=1.0, c1=0.1, c2=0.001, seed=3,
            beta1=0.9, beta2=0.999, eps=1e-8, wd=0.01)

    f32 = lambda: _aval(one_chip, (n,), jnp.float32)  # noqa: E731
    assert _kernels(fn, f32(), f32(), _aval(one_chip, (n,), m2_dtype), f32(),
                    _aval(one_chip, (), jnp.float32)) == 1


def test_fused_rms_norm_compiles(one_chip):
    import paddle_tpu.incubate.nn.functional as IF
    from paddle_tpu.core.tensor import Tensor

    def fn(x, w):
        return IF.fused_rms_norm(Tensor(x), Tensor(w)).value

    assert _kernels(fn, _aval(one_chip, (4096, 4096), BF16),
                    _aval(one_chip, (4096,), BF16)) == 1


# ---------------------------------------------------------------------------
# names: what a trace of the chip will call each kernel, and the path every
# op of a traced step carries
# ---------------------------------------------------------------------------

def _kernel_names(fn, *avals, **kw_avals):
    """Instruction names of the Pallas kernels in fn's program compiled for
    the described chip, version suffix dropped (`flash_fwd.1` -> `flash_fwd`):
    what chipbench/xplane.short_name keeps of a device event."""
    import re

    text = jax.jit(fn).lower(*avals, **kw_avals).compile().as_text()
    return sorted(re.sub(r"\.\d+$", "", m) for m in re.findall(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"" + KERNEL + "\"", text))


def _named_flash(sh):
    def grads(q, k, v):
        def loss(q, k, v):
            return pk.flash_attention_bshd(q, k, v).astype(jnp.float32).sum()

        return jax.grad(loss, (0, 1, 2))(q, k, v)

    q = _aval(sh, (16, 512, 12, 64), BF16)  # the trainer cell's shapes
    return _kernel_names(grads, q, q, q)


def _named_paged(sh):
    pages = _aval(sh, (256, 8, 16, 128), BF16)
    return (_kernel_names(pk.flash_decode_paged, _aval(sh, (8, 32, 128), BF16), pages, pages,
                          _aval(sh, (8, 16), jnp.int32), _aval(sh, (8,), jnp.int32))
            + _kernel_names(pk.flash_decode_paged_multi, _aval(sh, (8, 4, 32, 128), BF16), pages,
                            pages, _aval(sh, (8, 16), jnp.int32), _aval(sh, (8, 4), jnp.int32)))


def _named_adamw(sh):
    n = fo.pad_to_tile(1_000_000)

    def fn(p, m, v, g):
        return fo.fused_adamw_apply(p, m, v, g, lr=1e-3, clip_scale=1.0, c1=0.1, c2=0.001,
                                    seed=3, beta1=0.9, beta2=0.999, eps=1e-8, wd=0.01)

    f32 = _aval(sh, (n,), jnp.float32)
    return _kernel_names(fn, f32, f32, f32, f32)


def _named_rms_norm(sh):
    import paddle_tpu.incubate.nn.functional as IF
    from paddle_tpu.core.tensor import Tensor

    def fn(x, w):
        return IF.fused_rms_norm(Tensor(x), Tensor(w)).value

    return _kernel_names(fn, _aval(sh, (4096, 4096), BF16), _aval(sh, (4096,), BF16))


@pytest.mark.parametrize("build, names", [
    (_named_flash, ["flash_dkdv", "flash_dq", "flash_fwd"]),
    (_named_paged, ["paged_attn", "paged_attn"]),   # one pallas_call serves decode and extend
    (_named_adamw, ["fused_adamw"]),
    (_named_rms_norm, ["rms_norm"]),
], ids=["flash", "paged", "adamw", "rms_norm"])
def test_kernels_carry_stable_names(one_chip, build, names):
    """Whatever jit, jvp or transpose is around a kernel, the instruction the
    chip's trace shows is named after the kernel."""
    assert build(one_chip) == names


def test_train_step_hlo_carries_layer_loss_and_optimizer_paths(monkeypatch):
    """Compile-time metadata only (the host's own compiler will do): every
    op of a traced train step carries the path of the scopes around it."""
    import numpy as np

    monkeypatch.setattr(pk, "_on_tpu", lambda: False)  # a CPU program: no Mosaic kernels

    class Block(paddle.nn.Layer):
        def __init__(self):
            super().__init__()
            self.proj = paddle.nn.Linear(8, 8)

        def forward(self, x):
            return self.proj(x)

    class Net(paddle.nn.Layer):
        def __init__(self):
            super().__init__()
            self.layers = paddle.nn.LayerList([Block() for _ in range(4)])
            self.head = paddle.nn.Linear(8, 5)

        def forward(self, x):
            for layer in self.layers:
                x = layer(x)
            return self.head(x)

    net = Net()
    opt = paddle.optimizer.AdamW(learning_rate=0.01, parameters=net.parameters())

    @paddle.jit.to_static
    def train_step(x, y):
        loss = paddle.nn.functional.cross_entropy(net(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    x = paddle.to_tensor(np.ones((4, 8), "float32"))
    y = paddle.to_tensor(np.arange(4) % 5)
    for _ in range(2):
        train_step(x, y)
    (entry,) = [e for e in train_step.concrete_program().values() if e.jitted is not None]
    text = entry.jitted.as_text()
    import re

    for path in ("Net/layers.3/proj", "Net/head", "/loss/", "/optimizer/"):
        assert path in text, path
    # the pullbacks run later, in backward(), and carry their layer's path too
    assert re.search(r'op_name="[^"]*Net/layers\.3/proj/[^"]*transpose\(jvp', text)
    assert re.search(r'op_name="[^"]*/loss/[^"]*transpose\(jvp', text)

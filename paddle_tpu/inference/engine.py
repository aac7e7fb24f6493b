"""Decode-optimized inference engine: AOT shape buckets over the paged KV
cache and, for a model with recurrent layers, the recurrent-state cache
beside it.

The serving-tier compute core (PAPER.md L3c `jit/serving`). One engine owns:

- the model's parameter values (optionally placed on a mesh through PR 7's
  SpecLayout table — TP-sharded decode runs through the same code path);
- a BlockPool of paged KV (inference/kv_cache.py);
- a small set of AOT-COMPILED shape buckets: requests are padded into
  (batch=1, seq_bucket) prefill programs and (batch_bucket, 1) decode
  programs, and ONE chunk program (the largest batch bucket's rows and up
  to `chunk_width` prompt tokens of one more sequence, the weights read
  once for both), so steady-state serving never retraces — the same
  per-signature `lower().compile()` discipline the static Executor adopted
  in PR 5, with every compile recorded into the perf-attribution store
  (origin "serving") and bucket hits/compiles counted in telemetry.

Padding contract: prefill pads the prompt to the bucket on the right
(causal masking means real tokens never attend to the pad tail; the padded
tail's K/V writes land past `seq_len` — masked on every later read, and
overwritten by decode before the sequence grows into them). Decode pads
the batch with inactive rows whose block table is all trash-page and whose
seq_len is 1 — they compute garbage that is discarded.

Layer kinds: the model's `.config` may name each layer's kind
(`layer_kinds`: "attention", "mamba", "moe", or several joined by "+" where
one layer is both, as "attention+moe"; all attention when absent). The
pool holds pages for the attention layers only and one fixed-size state
slot a sequence for the recurrent ("mamba") ones; WHAT an attention layer
keeps a token is the model's `cache_entry` (absent: K and V, kv heads x
head_dim; `{"layout": "latent", "width": W}`: one latent vector, a pool of
`[N, bs, W]` pages; with `"index_width": I` a second array a layer,
`[N, bs, I]`, the keys of the model's token selector, and the step's spans
then count what the selector scored and chose by the model's `index_topk`);
the programs of a model with recurrent layers take each
row's slot as one more operand, and the chunk program the slot of the
chunk's sequence (resolved here from a sequence's first page: `decode`,
`prefill` and `decode_with_chunk` keep their signatures), and thread the
state arrays through with the pages; a model with expert layers returns
their counters beside the logits in the same fetch.

A decode step (`decode`, `decode_with_chunk`) is dispatched and not waited
for: its program chooses each row's token on the device (`argmax` over the
vocabulary, the first maximum as `np.argmax` takes it) and the call returns
a `StepResult`, which copies the ids (and the counters) to the host when
asked for them and the logits only when treated as an array. An entry of
`tokens` may be a `RowToken` of the step dispatched last: that row's input
is then the id the last step chose, taken on the device, so the next step can
be dispatched before the last one's ids are read. A chunk moves its
sequence's recurrent state forward only, from what its slot holds; going
back (`extend`) would need snapshots and is refused for such a model. For a
model whose layers are all attention every array and every operand is as it
was.
"""
from __future__ import annotations

import math
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
from jax import numpy as jnp

from .. import telemetry
from ..ops.pallas import mla_live_blocks, mla_page_blocks, paged_live_blocks, paged_page_blocks
from ..profiler.utils import RecordEvent
from ..telemetry import metrics as _metrics
from ..telemetry import request_trace as _rt
from .kv_cache import BlockPool, PagedCacheView, StateSpec

__all__ = ["InferenceEngine", "RowToken", "StepResult"]


def _bucket_counter():
    return _metrics.counter(
        "paddle_tpu_serving_bucket_events_total",
        "AOT shape-bucket cache events (hit = reused compiled program, "
        "compile = new signature lowered+compiled)",
        label_names=("kind", "event"),
    )


# Executables shared between the engines of one process: replicas with one
# bucket signature on one device set compile each bucket once, the first
# pays and the rest adopt (outcome "shared"). Keyed by `_bucket_key`'s entry
# key; FIFO-bounded, since sharing is a hint and a dropped entry only costs a
# compile.
_MAX_SHARED = 256
_shared_lock = threading.Lock()
_shared: Dict[str, object] = {}


def _shared_get(key: str):
    with _shared_lock:
        return _shared.get(key)


def _shared_put(key: str, compiled) -> None:
    with _shared_lock:
        if key not in _shared and len(_shared) >= _MAX_SHARED:
            _shared.pop(next(iter(_shared)))
        _shared[key] = compiled


def clear_shared_executables() -> None:
    """Forget every shared executable (tests start from an empty table, or
    one test's engine would donate its buckets to the next's)."""
    with _shared_lock:
        _shared.clear()


# Prompt tokens a chunk step carries beside the decode rows. A v5e's ridge is
# 197e12 FLOP/s over 819e9 B/s = 240 FLOP a byte, so about 240 tokens ride one
# read of the bf16 weights for nothing; 128 leaves the decode rows their half
# and is one lane tile of positions in the paged kernel. Rounded to whole
# pages by the engine: a chunk starts and ends on a page's edge.
_CHUNK_TOKENS = 128


def _default_prefill_buckets(max_seq_len: int, block_size: int) -> Tuple[int, ...]:
    out, b = [], max(16, block_size)
    while b < max_seq_len:
        out.append(b)
        b *= 2
    out.append(max_seq_len)
    return tuple(sorted(set(out)))


def _default_batch_buckets(max_batch: int) -> Tuple[int, ...]:
    out, b = [], 1
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return tuple(sorted(set(out)))


def _note_counters(args: dict, counts) -> None:
    """The expert layers' counters of one program onto its span's args."""
    args["moe_assignments"] = int(counts[0])
    args["moe_experts_touched"] = int(counts[1])
    args["moe_layers"] = int(counts[2])


class RowToken:
    """The token a dispatched step chose for one of its rows (`index` into the
    ids its program returned), as an entry of the next step's `tokens`: where
    `step` is the step the engine dispatched last, the value never leaves the
    device; anywhere else `int()` reads it."""

    __slots__ = ("step", "index")

    def __init__(self, step: "StepResult", index: int):
        self.step, self.index = step, index

    def __int__(self) -> int:
        return int(self.step._host_ids()[self.index])


class _Logits:
    """Logits that lie on the device until they are treated as an array."""

    __slots__ = ()

    def _host(self) -> np.ndarray:
        raise NotImplementedError

    def __array__(self, dtype=None, copy=None):
        a = self._host()
        return a if dtype is None else a.astype(dtype)

    def __getitem__(self, i):
        return self._host()[i]

    def __len__(self) -> int:
        return len(self._host())

    def __iter__(self):
        return iter(self._host())

    def __getattr__(self, name):  # shape, dtype, argmax, ...
        if name.startswith("_"):
            raise AttributeError(name)
        return getattr(self._host(), name)


class StepResult(_Logits):
    """What a dispatched decode step hands back: the logits `[n, V]` of its
    `n` rows when treated as an array (one copy, on first use), and

    - `ids()`: the token the program chose a row, int32 `[n]`: the step's ONE
      fetch for who serves greedy tokens (a few bytes, and for a model with
      expert layers its counters, which land on the step's `engine.decode`
      span's args whenever they arrive);
    - `token(i)`: row i's choice as an entry of the next step's `tokens`;
    - `chunk`: where the step carried a chunk, the same for the chunk's last
      token (logits `[V]`, `ids()` its one id, `token()`), else None.

    Waiting for either is an `engine.decode.fetch` span where it happens, and
    the first read stamps `read_at` (`time.perf_counter`, the ring's clock) on
    the step's span's args: the span ends at the dispatch, `read_at` is when
    the step's outputs were on the host. A result nobody reads costs nothing
    and leaves nothing behind."""

    __slots__ = ("chunk", "_out", "_n", "_bucket", "_args", "_ids", "_rows")

    def __init__(self, out, n: int, bucket: int, with_chunk: bool, args: dict):
        self._out = out  # (logits [B(+1), V], ids, counters where the model has expert layers), on the device
        self._n, self._bucket, self._args = n, bucket, args
        self._ids = self._rows = None
        self.chunk = _ChunkLast(self) if with_chunk else None

    def _read(self, logits: bool) -> None:
        want = self._out[0 if logits else 1:]
        with RecordEvent("engine.decode.fetch"):
            got = list(jax.device_get(want))
        if self._ids is None:  # the first read: when the step's outputs were on the host
            self._args["read_at"] = time.perf_counter()
        if logits:
            self._rows = got.pop(0)
        self._ids = got.pop(0)
        if got:
            _note_counters(self._args, got[0])

    def _host_ids(self) -> np.ndarray:
        if self._ids is None:
            self._read(logits=False)
        return self._ids

    def _host_rows(self) -> np.ndarray:
        if self._rows is None:
            self._read(logits=True)
        return self._rows

    def _host(self) -> np.ndarray:
        return self._host_rows()[:self._n]

    def ids(self) -> np.ndarray:
        return self._host_ids()[:self._n]

    def token(self, i: int) -> RowToken:
        return RowToken(self, i)


class _ChunkLast(_Logits):
    """The last token of a step's chunk: row `bucket` of the step's outputs."""

    __slots__ = ("step",)

    def __init__(self, step: StepResult):
        self.step = step

    def _host(self) -> np.ndarray:
        return self.step._host_rows()[self.step._bucket]

    def ids(self) -> np.ndarray:
        return self.step._host_ids()[self.step._bucket]

    def token(self) -> RowToken:
        return RowToken(self.step, self.step._bucket)


class InferenceEngine:
    """Greedy-decode serving engine over a paged KV cache.

    `model` is a causal LM with a `forward(ids, cache=, positions=,
    last_index=)` decode mode (LlamaForCausalLM, NemotronHForCausalLM) and a
    `.config` dict that holds `num_hidden_layers`, `num_attention_heads`,
    `hidden_size`, `vocab_size`, optionally `num_key_value_heads`,
    `head_dim` (default hidden_size // num_attention_heads),
    `layer_kinds` (one of "attention", "mamba", "moe" a layer, or several
    joined by "+"; default all attention) and `cache_entry` (what an
    attention layer caches a token; default K and V); with a "mamba" layer also `mamba_num_heads`,
    `mamba_head_dim`, `ssm_state_size`, `n_groups`, `conv_kernel`. `mesh` +
    `layout_table` place the weights for TP-sharded decode (PR 7
    SpecLayout); single-device when omitted.
    """

    def __init__(
        self,
        model,
        *,
        max_seq_len: int = 512,
        block_size: int = 16,
        num_blocks: Optional[int] = None,
        max_batch: int = 8,
        prefill_buckets: Optional[Sequence[int]] = None,
        decode_batch_buckets: Optional[Sequence[int]] = None,
        mesh=None,
        layout_table=None,
        kv_dtype: Optional[str] = None,
    ):
        from ..jit.api import state_values

        _t_init = time.monotonic()
        cfg = dict(getattr(model, "config", {}))
        if not cfg:
            raise ValueError(
                "InferenceEngine needs a model with a .config dict: num_hidden_layers, "
                "num_attention_heads, hidden_size, vocab_size, and optionally "
                "num_key_value_heads, head_dim, layer_kinds (see the class docstring)"
            )
        self._model = model
        self.num_layers = int(cfg["num_hidden_layers"])
        self.layer_kinds = tuple(cfg.get("layer_kinds") or ("attention",) * self.num_layers)
        if len(self.layer_kinds) != self.num_layers:
            raise ValueError(
                f"model.config names {len(self.layer_kinds)} layer kinds for {self.num_layers} layers")
        # layers that keep pages, layers that keep a recurrent state
        parts = [k.split("+") for k in self.layer_kinds]
        self.num_kv_layers = sum("attention" in p for p in parts)
        self.num_state_layers = sum("mamba" in p for p in parts)
        self._has_moe = any("moe" in p for p in parts)
        self.num_heads = int(cfg["num_attention_heads"])
        # what an attention layer caches a token: K and V a kv head, or the
        # model's own entry (one latent vector: a pool without a head axis)
        entry = cfg.get("cache_entry") or {"layout": "kv"}
        self.cache_layout = str(entry["layout"])
        # a model that selects cached tokens keeps index keys beside the entry
        # and attends over `index_topk` positions a query at most
        self.index_width = int(entry.get("index_width") or 0)
        self.index_topk = int(cfg.get("index_topk") or 0) if self.index_width else 0
        self.index_tile = int(cfg.get("index_query_tile") or 0)  # queries that select together
        self.index_totals = np.zeros((3,), np.int64)  # positions live, selected, sparse queries: all steps
        if self.cache_layout == "latent":
            self.num_kv_heads, self.head_dim = 1, int(entry["width"])
        else:
            self.num_kv_heads = int(cfg.get("num_key_value_heads") or self.num_heads)
            self.head_dim = int(cfg.get("head_dim") or int(cfg["hidden_size"]) // self.num_heads)
        self.vocab_size = int(cfg["vocab_size"])
        self.max_seq_len = int(max_seq_len)
        self.block_size = int(block_size)
        self.max_pages = math.ceil(self.max_seq_len / self.block_size)
        self.max_batch = int(max_batch)
        self.prefill_buckets = tuple(
            prefill_buckets or _default_prefill_buckets(self.max_seq_len, self.block_size)
        )
        if max(self.prefill_buckets) > self.max_pages * self.block_size:
            raise ValueError("prefill bucket exceeds the block-table capacity")
        self.decode_batch_buckets = tuple(
            decode_batch_buckets or _default_batch_buckets(self.max_batch)
        )

        params = state_values(model)
        w_dtype = params[next(iter(params))].dtype
        self._mesh = mesh
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            if layout_table is None:
                from ..distributed.sharding.spec_layout import transformer_layout_table

                layout_table = transformer_layout_table()
            self._param_shardings = {
                k: NamedSharding(mesh, layout_table.spec_for(k, v.shape))
                for k, v in params.items()
            }
            self.params = {
                k: jax.device_put(v, self._param_shardings[k]) for k, v in params.items()
            }
            self._repl = NamedSharding(mesh, P())
            # cache pages follow the TP layout: k/v come out of the
            # column-sharded k/v_proj per-head, so each tp rank holds its kv
            # heads' pages (no gather on the decode read); replicated when
            # the head count doesn't divide
            tp_axis = layout_table.layout.tp_axis
            tp_deg = int(mesh.shape.get(tp_axis, 1))
            if tp_deg > 1 and self.cache_layout == "kv" and self.num_kv_heads % tp_deg == 0:
                self._page_sharding = NamedSharding(mesh, P(None, tp_axis, None, None))
            else:
                self._page_sharding = self._repl
        else:
            self._param_shardings = None
            self.params = params
            self._repl = None
            self._page_sharding = None

        # prompt tokens a step may carry beside its decode rows (`decode_with_chunk`):
        # whole pages, at most the table
        self.chunk_width = min(
            max(self.block_size, _CHUNK_TOKENS // self.block_size * self.block_size),
            self.max_pages * self.block_size)

        if num_blocks is None:
            # worst case: every decode slot at full context, plus the trash page
            num_blocks = 1 + self.max_batch * self.max_pages
        state_spec = None
        if self.num_state_layers:
            m_heads, m_dim = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
            m_state = int(cfg["ssm_state_size"])
            state_spec = StateSpec(
                m_heads, m_dim, m_state, int(cfg["conv_kernel"]) - 1,
                m_heads * m_dim + 2 * int(cfg["n_groups"]) * m_state)
        self.pool = BlockPool(
            num_blocks, self.block_size, self.num_kv_layers,
            self.num_kv_heads, self.head_dim, dtype=w_dtype,
            kv_dtype=kv_dtype, state_layers=self.num_state_layers,
            state_spec=state_spec, state_slots=self.max_batch, layout=self.cache_layout,
            index_width=self.index_width,
        )
        # donation keeps exactly one pool copy live on TPU; CPU's donation
        # path only warns, so gate it on the platform
        self._donate = jax.devices()[0].platform == "tpu"
        self._compiled: Dict[Tuple[str, int], object] = {}
        # ids a decode or chunk program hands back: one length for every
        # bucket (the largest's rows and a chunk's last token), so that any
        # of them can take its rows' tokens from any other's
        self._ids_width = self.decode_batch_buckets[-1] + 1
        self._last_step: Optional[StepResult] = None  # the decode step dispatched last
        self._no_ids = None  # what a step takes as the last step's ids where there is none
        self.bucket_stats = {"hits": 0, "compiles": 0}
        # bumped by every load_weights(); the fleet exports it per replica
        # so a half-finished rollout is visible in telemetry
        self.weights_version = 0
        # per-signature fingerprints (lazy), and the cold-start timeline
        # marks the `compile_cache report` decomposes
        self._fingerprints: Dict[Tuple[str, object], Tuple[str, str]] = {}
        self._fp_base: Optional[str] = None
        self._topo_meta: Optional[dict] = None
        self._first_token_marked = False
        if telemetry.enabled():
            from .. import compile_cache as _cc

            _cc.ledger.mark("engine_load_start", _t_init)
            _cc.ledger.span("engine_init", _t_init, time.monotonic())

    # ---- zero-downtime weight hot-swap hooks ----
    def load_weights(self, state) -> int:
        """Swap in a full replacement parameter set WITHOUT recompiling.

        `state` maps every param name (exactly the engine's own key set) to
        an array/Tensor of identical shape; dtype is cast to the current
        param's. New values are placed under the engine's PINNED shardings
        (`_param_shardings`), so the AOT-compiled prefill/decode programs —
        whose in/out shardings were pinned at compile time — accept them
        as-is and the threaded cache pages keep their layout: this is the
        invariant that makes a live swap safe mid-traffic. Returns the new
        weights_version."""
        vals = {
            k: (v._value if hasattr(v, "_value") else v) for k, v in state.items()
        }
        missing = set(self.params) - set(vals)
        extra = set(vals) - set(self.params)
        if missing or extra:
            raise ValueError(
                f"load_weights: state keys do not match the engine's params "
                f"(missing {sorted(missing)[:3]}, unexpected {sorted(extra)[:3]})"
            )
        new = {}
        for k, cur in self.params.items():
            v = jnp.asarray(vals[k])
            if tuple(v.shape) != tuple(cur.shape):
                raise ValueError(
                    f"load_weights: {k!r} shape {tuple(v.shape)} != engine's "
                    f"{tuple(cur.shape)} — a hot swap cannot change the model"
                )
            if v.dtype != cur.dtype:
                v = v.astype(cur.dtype)
            if self._param_shardings is not None:
                v = jax.device_put(v, self._param_shardings[k])
            else:
                v = jax.device_put(v)
            new[k] = v
        self.params = new
        self.weights_version += 1
        # resident prefix-cache K/V was computed under the OLD weights — a
        # post-swap hit would mix old-weight keys/values into new-weight
        # attention; drop the index (active requests' own pages are
        # unaffected: the drained-replica swap protocol means there are
        # none, and any stragglers just lose shareability)
        self.pool.invalidate_prefix()
        if telemetry.enabled():
            _metrics.counter(
                "paddle_tpu_serving_weight_swaps_total",
                "engine parameter sets hot-swapped under pinned shardings",
            ).inc()
        return self.weights_version

    def checkpoint_template(self, state_key: Optional[str] = "model"):
        """A DETACHED Tensor template shaped and placed like the engine's
        pinned params, for `distributed.checkpoint.load_state_dict` —
        detached so streaming a checkpoint in never mutates the live model
        object other replicas may still be serving from."""
        from ..core.tensor import Tensor

        tpl = {k: Tensor(v) for k, v in self.params.items()}
        return {state_key: tpl} if state_key else tpl

    def load_weights_from_checkpoint(self, path: str, state_key: Optional[str] = "model") -> int:
        """Stream a topology-portable `step_<N>/` checkpoint (PR 7 format;
        newest COMPLETE step under `path` wins, reshard-on-load included)
        into this engine's pinned placements and swap it live. `state_key`
        is the key the training loop saved the model state under
        (`save_state_dict({"model": ...})`); None for a bare layout."""
        from ..distributed import checkpoint as _ckpt

        tpl = self.checkpoint_template(state_key)
        _ckpt.load_state_dict(tpl, path)
        return self.load_weights(tpl[state_key] if state_key else tpl)

    # ---- buckets ----
    def bucket_for(self, kind: str, n: int) -> int:
        buckets = self.prefill_buckets if kind == "prefill" else self.decode_batch_buckets
        for b in buckets:
            if n <= b:
                return b
        raise ValueError(f"{kind} size {n} exceeds the largest bucket {buckets[-1]}")

    def _bucket_key(self, kind: str, size) -> Tuple[str, str]:
        """(program fingerprint, share key) for one bucket
        signature — a canonical text over everything the compiled artifact
        depends on (dims, bucket, pool/state avals, param avals, donation,
        shardings) and nothing it doesn't: weight VALUES are call
        arguments, so same-signature replicas share by construction."""
        cached = self._fingerprints.get((kind, size))
        if cached is not None:
            return cached
        from .. import compile_cache as _cc

        if self._fp_base is None:
            shard_txt = "none"
            if self._param_shardings is not None:
                shard_txt = ";".join(
                    f"{k}={s.spec}" for k, s in sorted(self._param_shardings.items())
                ) + f"|pages={self._page_sharding.spec}"
            self._fp_base = "|".join((
                "serving-bucket-v1",
                _cc.aval_signature(self._param_avals()),
                _cc.aval_signature(self._state_avals()),
                f"block={self.block_size},pages={self.max_pages},"
                f"vocab={self.vocab_size},donate={self._donate},ids={self._ids_width}",
                f"model={type(self._model).__name__}",
                shard_txt,
            ))
            self._topo_meta = _cc.topology_meta(self._mesh)
        sz = size if isinstance(size, int) else "x".join(str(s) for s in size)
        fp = _cc.fingerprint_text(f"{self._fp_base}|{kind}:{sz}")
        out = (fp, _cc.entry_key(fp, self._topo_meta))
        self._fingerprints[(kind, size)] = out
        return out

    def _get_compiled(self, kind: str, size):
        key = (kind, size)
        # extend signatures are (B, Q) pairs; everything downstream wants a
        # flat printable size ("4x4") rather than a tuple repr
        sz = size if isinstance(size, int) else "x".join(str(s) for s in size)
        ex = self._compiled.get(key)
        if ex is not None:
            # a dictionary lookup, every served step: the bucket counter only
            # (the compile ledger counts compiles, misses and shared programs)
            self.bucket_stats["hits"] += 1
            if telemetry.enabled():
                _bucket_counter().labels(kind=kind, event="hit").inc()
            if _rt.enabled():
                _rt.record_event("engine", "dispatch", kind=kind, size=sz,
                                 event="hit")
            return ex
        # a miss inside a serving step is a named span in the step that
        # paid for it
        with RecordEvent("engine.compile", args={"kind": kind, "size": sz}) as span:
            ex, outcome = self._compile_miss(kind, size, sz)
            span.args["outcome"] = "compile" if outcome == "miss" else outcome
        if ((kind, size) == ("decode", self.decode_batch_buckets[-1])
                and self.chunk_width and self.max_batch > 1):
            # who readies the largest decode bucket readies what a step beside
            # it may run: a prompt's chunk rides the decode rows only where a
            # second slot exists (a lone slot's chunk program compiles on
            # first use, after a prefix-cache hit)
            self._get_compiled("chunk", size)
        return ex

    def _compile_miss(self, kind: str, size, sz):
        """(executable, outcome) for a bucket this engine has not run yet:
        shared from a same-signature engine of this process, or compiled
        (outcome "miss")."""
        from .. import compile_cache as _cc

        key = (kind, size)
        name = f"{kind}_{sz}"
        t0 = time.perf_counter()
        fp, ekey = self._bucket_key(kind, size)
        ex = _shared_get(ekey)
        outcome = "miss" if ex is None else "shared"
        if ex is None:
            if kind == "prefill":
                ex = self._compile_prefill(size)
            elif kind == "decode":
                ex = self._compile_decode(size)
            elif kind == "chunk":
                ex = self._compile_chunk(size)
            else:  # ("extend", (B, Q))
                ex = self._compile_extend(*size)
            _shared_put(ekey, ex)
        dt = time.perf_counter() - t0
        self._compiled[key] = ex
        if outcome == "miss":
            self.bucket_stats["compiles"] += 1
        else:
            # the key appears only when sharing happens: the baseline
            # {hits, compiles} shape is unchanged for a lone engine
            self.bucket_stats["shared"] = self.bucket_stats.get("shared", 0) + 1
        event = "compile" if outcome == "miss" else outcome
        if _rt.enabled():
            # a compile-miss dispatch IS a tail-latency event: the signature
            # + wall time land in the trace so a bucket-miss-shaped p99 blip
            # is attributable instead of mysterious
            _rt.record_event("engine", "dispatch", kind=kind, size=sz,
                             event=event, dur_s=round(dt, 6))
        _cc.record("serving", name, outcome, seconds=dt, fingerprint=fp,
                   signature=sz)
        if telemetry.enabled():
            _bucket_counter().labels(kind=kind, event=event).inc()
            if outcome == "miss":
                try:
                    from ..profiler import perf_attribution as _pa

                    _pa.record_compiled(
                        "serving", name, compiled=ex, compile_seconds=dt
                    )
                except Exception:
                    pass
        return ex, outcome

    def prewarm(self, *, include_prefill: bool = True,
                include_decode: bool = True,
                extend_q: Sequence[int] = ()) -> dict:
        """Compile (or share) every bucket program up front, so
        steady-state serving — and the first token — never pays a compile
        (the chunk program comes with the largest decode bucket).
        `extend_q` adds the (B, Q) extend/verify family for the given
        query lengths (speculative decode uses draft_len + 1);
        `include_prefill=False` warms a decode-tier engine (streamed
        admission never runs a bucketed prefill, so the prefill family
        would be dead weight in its compile ledger). Records the `prewarm`
        span the cold-start report decomposes. Returns a copy of
        bucket_stats."""
        t0 = time.monotonic()
        if include_prefill:
            for S in self.prefill_buckets:
                self._get_compiled("prefill", S)
        if include_decode:
            for B in self.decode_batch_buckets:
                self._get_compiled("decode", B)
        for q in extend_q:
            for B in self.decode_batch_buckets:
                self._get_compiled("extend", (B, int(q)))
        if telemetry.enabled():
            from .. import compile_cache as _cc

            _cc.ledger.span("prewarm", t0, time.monotonic())
        return dict(self.bucket_stats)

    def _mark_first_token(self) -> None:
        if self._first_token_marked:
            return
        self._first_token_marked = True
        if telemetry.enabled():
            from .. import compile_cache as _cc

            _cc.ledger.mark("first_token")

    def _state_avals(self):
        """Avals mirroring pool.device_state(): per-layer page arrays plus
        scale planes on a quantized pool — the ONE pytree every compiled
        step threads through (and donates)."""
        shape = self.pool.page_shape
        one = jax.ShapeDtypeStruct(shape, self.pool.dtype)
        avals = {"k": [one] * self.num_kv_layers, "v": [one] * len(self.pool.v_pages)}
        if self.pool.quantized:
            sc = jax.ShapeDtypeStruct(shape[:3], jnp.float32)
            avals["k_scale"] = [sc] * self.num_kv_layers
            avals["v_scale"] = [sc] * self.num_kv_layers
        if self.num_state_layers:
            for key in ("ssm", "conv"):
                avals[key] = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in getattr(self.pool, key)]
        if self.index_width:
            avals["index"] = [jax.ShapeDtypeStruct((*shape[:2], self.index_width), self.pool.dtype)
                              ] * self.num_kv_layers
        return avals

    def _state_shardings(self):
        """NamedShardings matching _state_avals: pages follow the kv-head
        TP split; scale planes share it (their head axis is axis 1 too)."""
        pages = [self._page_sharding] * self.num_kv_layers
        sh = {"k": pages, "v": pages[:len(self.pool.v_pages)]}
        if self.pool.quantized:
            if self._page_sharding is not self._repl:
                from jax.sharding import NamedSharding, PartitionSpec as P

                spec = self._page_sharding.spec
                sc = NamedSharding(self._mesh, P(*spec[:3]))
            else:
                sc = self._repl
            sh["k_scale"] = [sc] * self.num_kv_layers
            sh["v_scale"] = [sc] * self.num_kv_layers
        if self.num_state_layers:
            # a recurrent layer's state is whole on every device
            sh["ssm"] = [self._repl] * self.num_state_layers
            sh["conv"] = [self._repl] * self.num_state_layers
        if self.index_width:
            sh["index"] = pages
        return sh

    def _slot_avals(self, rows: int):
        """The operand a model with recurrent layers adds to each program,
        before the state: every row's state slot (the chunk program takes
        two, its rows' and, one row, the chunk's sequence's)."""
        return (jax.ShapeDtypeStruct((rows,), jnp.int32),) if self.num_state_layers else ()

    def _slots_of(self, page_rows, rows: int):
        """That operand's value: each row's slot by its first page, the
        trash slot for a row with no pages and for the bucket's padding."""
        if not self.num_state_layers:
            return ()
        slots = np.zeros((rows,), np.int32)
        for i, row in enumerate(page_rows):
            if len(row):
                slots[i] = self.pool.state_slot(row[0])
        return (jnp.asarray(slots),)

    @staticmethod
    def _with_counters(logits, view):
        """What a program hands back to the host: the logits, and beside
        them the expert layers' counters where the model has such layers."""
        return logits if view.moe_counts is None else (logits, view.moe_counts)

    def _step_outputs(self, logits, view):
        """What a decode or chunk program hands back beside the state: the
        rows' logits, the token each row's logits choose (greedy: the first
        maximum, as `np.argmax` takes it on the same array), padded to
        `_ids_width`, and the expert layers' counters where the model has
        such layers."""
        ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        ids = jnp.pad(ids, (0, self._ids_width - ids.shape[0]))
        return (logits, ids) if view.moe_counts is None else (logits, ids, view.moe_counts)

    @staticmethod
    def _row_tokens(tokens, prev_ids, src):
        """Each row's input token: the host's, or where `src` names a row of
        the last step's ids (not -1), the id that step chose."""
        return jnp.where(src >= 0, prev_ids[jnp.maximum(src, 0)], tokens)

    def _fetch(self, out, span):
        """A prefill's ONE fetch: its one row of logits and, for a model with
        expert layers, its counters onto the span."""
        if not self._has_moe:
            return np.asarray(out[0])
        logits, counts = jax.device_get((out[0][0], out[1]))
        _note_counters(span.args, counts)
        return logits

    def _jit(self, fn, n_args: int):
        """fn's signature is (params, *scalars, cache_state) with the state
        pytree LAST (argnum n_args - 1): donated (TPU), sharded per
        _state_shardings, and pinned on the outputs so threaded pages keep
        one layout across programs."""
        kwargs = {}
        if self._donate:
            # the state pytree is threaded through every step — alias it
            kwargs["donate_argnums"] = (n_args - 1,)
        if self._param_shardings is not None:
            repl = self._repl
            kwargs["in_shardings"] = (
                self._param_shardings,
                *([repl] * (n_args - 2)),
                self._state_shardings(),
            )
            # pin the outputs too: prefill/decode THREAD the pages — without
            # this GSPMD picks per-program layouts and the next program's
            # compiled signature rejects them
            kwargs["out_shardings"] = (repl, self._state_shardings())
        return jax.jit(fn, **kwargs)

    def _param_avals(self):
        return {
            k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in self.params.items()
        }

    def _compile_prefill(self, S: int):
        from ..core.tensor import Tensor
        from ..jit.api import functional_call
        from ..autograd import no_grad

        model, block_size = self._model, self.block_size
        with_counters = self._with_counters

        def fn(params, ids, true_len, bt, *rest):
            *slots, state = rest
            view = PagedCacheView.from_state(state, bt, true_len, block_size, slots=slots[0] if slots else None)
            with no_grad():
                logits = functional_call(
                    model, params, Tensor(ids), cache=view,
                    last_index=true_len - 1, training=False,
                )
            return with_counters(logits.value, view), PagedCacheView.state_of(view)

        i32 = jnp.int32
        avals = (
            self._param_avals(),
            jax.ShapeDtypeStruct((1, S), i32),
            jax.ShapeDtypeStruct((1,), i32),
            jax.ShapeDtypeStruct((1, self.max_pages), i32),
            *self._slot_avals(1),
            self._state_avals(),
        )
        return self._jit(fn, len(avals)).lower(*avals).compile()

    def _compile_decode(self, B: int):
        from ..core.tensor import Tensor
        from ..jit.api import functional_call
        from ..autograd import no_grad

        model, block_size = self._model, self.block_size
        outputs, row_tokens = self._step_outputs, self._row_tokens

        def fn(params, tokens, positions, seq_lens, bt, prev_ids, src, *rest):
            *slots, state = rest
            view = PagedCacheView.from_state(state, bt, seq_lens, block_size, slots=slots[0] if slots else None)
            tokens = row_tokens(tokens, prev_ids, src)
            with no_grad():
                logits = functional_call(
                    model, params, Tensor(tokens[:, None]), cache=view,
                    positions=positions, training=False,
                )
            return outputs(logits.value[:, 0], view), PagedCacheView.state_of(view)

        i32 = jnp.int32
        avals = (
            self._param_avals(),
            jax.ShapeDtypeStruct((B,), i32),
            jax.ShapeDtypeStruct((B,), i32),
            jax.ShapeDtypeStruct((B,), i32),
            jax.ShapeDtypeStruct((B, self.max_pages), i32),
            jax.ShapeDtypeStruct((self._ids_width,), i32),
            jax.ShapeDtypeStruct((B,), i32),
            *self._slot_avals(B),
            self._state_avals(),
        )
        return self._jit(fn, len(avals)).lower(*avals).compile()

    def _compile_chunk(self, B: int):
        """The chunk program: B decode rows' one token each and `chunk_width`
        consecutive prompt tokens of ONE more sequence, all in one row of
        tokens, so every weight matmul runs once over both and the weights
        are read once a step. Attention splits by segment (the model's cache
        branch, on the view's `chunk_table`); the vocabulary head runs on the
        rows and the chunk's last real token only (`last`), so the logits
        are [B + 1, V] and the ids [B + 1], the chunk's last. A row's token
        may be the last step's, as in the decode program; the chunk's tokens
        are the prompt's and the host's."""
        from ..core.tensor import Tensor
        from ..jit.api import functional_call
        from ..autograd import no_grad

        model, block_size = self._model, self.block_size
        outputs, row_tokens = self._step_outputs, self._row_tokens

        def fn(params, tokens, positions, seq_lens, bt, chunk_bt, last, prev_ids, src, *rest):
            *slots, state = rest
            slots, chunk_slot = slots or (None, None)  # where the model keeps recurrent state
            view = PagedCacheView.from_state(state, bt, seq_lens, block_size, slots=slots,
                                             chunk_table=chunk_bt, chunk_slot=chunk_slot)
            tokens = jnp.concatenate([row_tokens(tokens[:, :B], prev_ids, src[None]), tokens[:, B:]], axis=1)
            with no_grad():
                logits = functional_call(
                    model, params, Tensor(tokens), cache=view,
                    positions=positions, last_index=last, training=False,
                )
            return outputs(logits.value, view), PagedCacheView.state_of(view)

        i32 = jnp.int32
        avals = (
            self._param_avals(),
            jax.ShapeDtypeStruct((1, B + self.chunk_width), i32),
            jax.ShapeDtypeStruct((1, B + self.chunk_width), i32),
            jax.ShapeDtypeStruct((B,), i32),
            jax.ShapeDtypeStruct((B, self.max_pages), i32),
            jax.ShapeDtypeStruct((1, self.max_pages), i32),
            jax.ShapeDtypeStruct((B + 1,), i32),
            jax.ShapeDtypeStruct((self._ids_width,), i32),
            jax.ShapeDtypeStruct((B,), i32),
            *self._slot_avals(B),
            *self._slot_avals(1),
            self._state_avals(),
        )
        return self._jit(fn, len(avals)).lower(*avals).compile()

    def _compile_extend(self, B: int, Q: int):
        """The extend/verify program (round 17): Q tokens per row written +
        read through the paged cache in ONE call — speculative-decode
        verify (1 committed token + k drafts), and with it the prompts of a
        scheduler that runs `spec_decode` (Q tokens a row a step; without it
        a prompt enters through `_compile_chunk`'s program). `valid` masks
        pad slots: their K/V writes are redirected to the trash page and
        their logits are discarded host-side."""
        from ..core.tensor import Tensor
        from ..jit.api import functional_call
        from ..autograd import no_grad

        model, block_size = self._model, self.block_size

        def fn(params, tokens, positions, valid, bt, state):
            view = PagedCacheView.from_state(state, bt, positions[:, -1] + 1, block_size,
                                             write_mask=valid)
            with no_grad():
                logits = functional_call(
                    model, params, Tensor(tokens), cache=view,
                    positions=positions, training=False,
                )
            return logits.value, PagedCacheView.state_of(view)

        i32 = jnp.int32
        avals = (
            self._param_avals(),
            jax.ShapeDtypeStruct((B, Q), i32),
            jax.ShapeDtypeStruct((B, Q), i32),
            jax.ShapeDtypeStruct((B, Q), jnp.bool_),
            jax.ShapeDtypeStruct((B, self.max_pages), i32),
            self._state_avals(),
        )
        return self._jit(fn, 6).lower(*avals).compile()

    # ---- steps ----
    def prefill(self, prompt_ids: Sequence[int], pages: Sequence[int]) -> np.ndarray:
        """Run one prompt through a prefill bucket, writing its K/V into
        `pages`; returns the last-position logits [V]."""
        L = len(prompt_ids)
        if L < 1 or L > self.max_seq_len:
            raise ValueError(f"prompt length {L} outside [1, {self.max_seq_len}]")
        S = self.bucket_for("prefill", L)
        with RecordEvent("engine.prefill", args={"tokens": L, "bucket": S}) as span:
            with RecordEvent("engine.prefill.inputs"):
                ids = np.zeros((1, S), np.int32)
                ids[0, :L] = np.asarray(prompt_ids, np.int32)
                bt = np.asarray([self.pool.padded_table(pages, self.max_pages)], np.int32)
                slots = self._slots_of([pages], 1)
                if slots:
                    span.args["state_slots"] = self.pool.state_slots_used()
                if self.index_topk:
                    tile = self.index_tile or L
                    for start in range(0, L, tile):
                        self._count_index(span, [start], [min(tile, L - start)])
            ex = self._get_compiled("prefill", S)
            with RecordEvent("engine.prefill.dispatch"):
                logits, state = ex(
                    self.params, jnp.asarray(ids), jnp.asarray([L], jnp.int32),
                    jnp.asarray(bt), *slots, self.pool.device_state(),
                )
                self.pool.adopt_state(state)
            with RecordEvent("engine.prefill.fetch"):
                out = self._fetch(logits, span)
        self._mark_first_token()
        return out

    def _count_page_blocks(self, span, first, count, q_len: int = 1):
        """What the paged kernel's grid does with ONE of its calls, added up
        on the span: `page_blocks_grid` steps a layer (bucket rows x page
        blocks of the table; over latent pages, x the row's query tiles), of
        which `page_blocks_live` reach a page someone wrote (up to each row's
        frontier, pad rows one) and the rest start no copy. Row i's queries
        stand at `first[i]` .. `first[i] + count[i] - 1`, `q_len` slots a row."""
        if self.cache_layout == "latent":
            live = mla_live_blocks(first, count, q_len, self.num_heads, self.block_size, self.max_pages)
            _, blocks = mla_page_blocks(self.block_size, self.max_pages)
        else:
            live = paged_live_blocks(first + count - 1, self.block_size, self.max_pages)
            _, blocks = paged_page_blocks(self.block_size, self.max_pages)
        span.args["page_blocks_live"] = span.args.get("page_blocks_live", 0) + int(live.sum())
        span.args["page_blocks_grid"] = span.args.get("page_blocks_grid", 0) + int(live.size * blocks)

    def _count_index(self, span, first, count):
        """What the token selector does with ONE tile of a step's queries (the
        queries that score, select and attend together: a step's decode rows,
        a prompt's chunk, `index_tile` consecutive queries of a longer row),
        added up on the span (a layer; every layer does the same). Row i's
        `count[i]` queries stand at `first[i]` on, each with a context of its
        position + 1. `index_positions_live` is the sum of those contexts,
        `index_positions_selected` the positions attended (`index_topk` a query
        at most), `sparse_queries` the queries whose context is past
        `index_topk`. A tile with such a query goes through the selector whole:
        `index_positions_scored` / `sparse_positions_attended` are its share of
        the first two, `index_keys_read` the index keys it reads (each row's
        context once)."""
        if not self.index_topk:
            return
        first, count, k = np.asarray(first, np.int64), np.asarray(count, np.int64), self.index_topk
        last = first + count  # one past the last query's context
        live = int((count * (first + last + 1) // 2).sum())
        dense = np.clip(k - first, 0, count)  # queries whose whole context is chosen
        selected = int((dense * (2 * first + dense + 1) // 2 + (count - dense) * k).sum())
        add = {"index_positions_live": live, "index_positions_selected": selected,
               "sparse_queries": int((count - dense).sum())}
        if add["sparse_queries"]:
            add.update(index_positions_scored=live, sparse_positions_attended=selected,
                       index_keys_read=int(last.sum()))
        self.index_totals += np.asarray([live, selected, add["sparse_queries"]], np.int64)
        for key, v in add.items():
            span.args[key] = span.args.get(key, 0) + v

    def decode(
        self,
        tokens: Sequence,
        positions: Sequence[int],
        seq_lens: Sequence[int],
        page_rows: Sequence[Sequence[int]],
    ) -> StepResult:
        """One decode step for `n` in-flight sequences (token i at absolute
        position positions[i], context length seq_lens[i] AFTER this token;
        a token is an int, or a `RowToken` of an earlier step). Dispatched,
        not waited for: the `StepResult` is the logits [n, V] when treated as
        an array, and its `ids()` the token each row's logits choose."""
        if len(tokens) < 1:
            raise ValueError("decode needs at least one sequence")
        return self._decode_step(tokens, positions, seq_lens, page_rows)

    def decode_with_chunk(
        self,
        tokens: Sequence,
        positions: Sequence[int],
        seq_lens: Sequence[int],
        page_rows: Sequence[Sequence[int]],
        chunk_ids: Sequence[int],
        chunk_start: int,
        chunk_pages: Sequence[int],
    ) -> Tuple[StepResult, "_ChunkLast"]:
        """A decode step (as `decode`; `n` may be 0) that also carries the
        next 1..`chunk_width` prompt tokens `chunk_ids` of ONE more sequence,
        at positions chunk_start.. of `chunk_pages` (chunk_start on a page's
        edge; the pages cover the chunk's last token). One program, the
        weights read once: the chunk's K/V is written by whole pages and its
        tokens see the sequence's cached context and themselves causally; a
        recurrent layer takes the chunk forward from the state its sequence's
        slot holds (zeros at chunk_start 0) and leaves the new state there.
        Returns (the step's result: logits [n, V], the result's `chunk`: the
        chunk's LAST token's logits [V]). The span is an `engine.decode` like
        any step's, with `chunk_tokens`."""
        take = len(chunk_ids)
        if take < 1 or take > self.chunk_width:
            raise ValueError(f"a chunk holds 1..{self.chunk_width} tokens, not {take}")
        if chunk_start % self.block_size or chunk_start + take > self.max_seq_len:
            raise ValueError(
                f"a chunk starts on a page's edge and ends inside the table: start {chunk_start}, "
                f"{take} tokens, pages of {self.block_size}, max_seq_len {self.max_seq_len}")
        step = self._decode_step(tokens, positions, seq_lens, page_rows,
                                 (chunk_ids, int(chunk_start), chunk_pages))
        return step, step.chunk

    def _decode_step(self, tokens, positions, seq_lens, page_rows, chunk=None) -> StepResult:
        """`decode` and `decode_with_chunk`: the rows into their bucket (the
        largest, the chunk program's, where a chunk rides), the chunk's
        tokens behind them; dispatched and handed back unread."""
        n = len(tokens)
        ids, start, pages = chunk or ((), 0, ())
        take = len(ids)
        B = self.bucket_for("decode", n) if chunk is None else self.decode_batch_buckets[-1]
        C = self.chunk_width if chunk is not None else 0
        args = {"rows": n, "bucket": B, "chunk_tokens": take, "chunk_width": self.chunk_width}
        prev = self._last_step
        with RecordEvent("engine.decode", args=args) as span:
            with RecordEvent("engine.decode.inputs"):
                tok = np.zeros((B + C,), np.int32)
                pos = np.zeros((B + C,), np.int32)
                lens = np.ones((B,), np.int32)  # inactive rows read 1 trash slot
                bt = np.zeros((B, self.max_pages), np.int32)
                src = np.full((B,), -1, np.int32)  # the host's token
                for i, t in enumerate(tokens):
                    if type(t) is RowToken and t.step is prev:
                        src[i] = t.index  # still on the device: the last step's choice
                    else:
                        tok[i] = int(t)
                pos[:n] = np.asarray(positions, np.int32)
                lens[:n] = np.asarray(seq_lens, np.int32)
                for i, row in enumerate(page_rows):
                    bt[i] = self.pool.padded_table(row, self.max_pages)
                span.args["context"] = int(lens[:n].sum())
                self._count_page_blocks(span, lens - 1, np.ones_like(lens))
                self._count_index(span, lens[:n] - 1, np.ones((n,), np.int64))
                if chunk is not None:
                    tok[B:B + take] = np.asarray(ids, np.int32)
                    pos[B:B + take] = start + np.arange(take, dtype=np.int32)  # pad slots stay at 0
                    chunk_bt = np.asarray([self.pool.padded_table(pages, self.max_pages)], np.int32)
                    last = np.append(np.arange(B, dtype=np.int32), np.int32(B + take - 1))
                    span.args["context"] += start + take
                    span.args["chunk_context"] = start
                    self._count_page_blocks(span, np.asarray([start]), np.asarray([take]), C)
                    self._count_index(span, [start], [take])
                slots = self._slots_of(page_rows, B)
                if chunk is not None:
                    slots += self._slots_of([pages], 1)
                if slots:
                    span.args["state_slots"] = self.pool.state_slots_used()
            if prev is not None:
                prev_ids = prev._out[1]
            else:
                if self._no_ids is None:
                    self._no_ids = jnp.zeros((self._ids_width,), jnp.int32)
                prev_ids = self._no_ids
            ex = self._get_compiled("decode" if chunk is None else "chunk", B)
            with RecordEvent("engine.decode.dispatch"):  # the transfers, the call, the state adopted
                if chunk is None:
                    operands = (jnp.asarray(tok), jnp.asarray(pos), jnp.asarray(lens), jnp.asarray(bt),
                                prev_ids, jnp.asarray(src), *slots)
                else:
                    operands = (jnp.asarray(tok[None]), jnp.asarray(pos[None]), jnp.asarray(lens),
                                jnp.asarray(bt), jnp.asarray(chunk_bt), jnp.asarray(last),
                                prev_ids, jnp.asarray(src), *slots)
                out, state = ex(self.params, *operands, self.pool.device_state())
                self.pool.adopt_state(state)
        self._mark_first_token()
        self._last_step = StepResult(out, n, B, chunk is not None, args)
        return self._last_step

    def extend(
        self,
        token_rows: Sequence[Sequence[int]],
        position_rows: Sequence[Sequence[int]],
        page_rows: Sequence[Sequence[int]],
        q_len: int,
    ) -> np.ndarray:
        """One extend/verify step: row i consumes len(token_rows[i]) <=
        q_len consecutive tokens at position_rows[i], writing their K/V and
        returning next-token logits for EVERY consumed position —
        [n, q_len, V] (pad slots hold garbage; callers read only their real
        prefix). Speculative verify reads the whole greedy chain from one
        call; a scheduler that speculates also streams its prompts here,
        q_len tokens a row a step (without `spec_decode` a prompt enters
        through `decode_with_chunk`)."""
        n = len(token_rows)
        if n < 1:
            raise ValueError("extend needs at least one sequence")
        if self.num_state_layers:
            raise NotImplementedError(
                "extend: the model has recurrent layers, and a verify that may go back over a "
                "live recurrent state needs state snapshots (a prompt's chunk, which only goes "
                "forward, enters through decode_with_chunk)")
        B = self.bucket_for("decode", n)
        with RecordEvent("engine.extend", args={"rows": n, "bucket": B, "q_len": q_len}) as span:
            with RecordEvent("engine.extend.inputs"):
                tok = np.zeros((B, q_len), np.int32)
                pos = np.zeros((B, q_len), np.int32)
                valid = np.zeros((B, q_len), bool)
                bt = np.zeros((B, self.max_pages), np.int32)
                for i, (toks, poss) in enumerate(zip(token_rows, position_rows)):
                    r = len(toks)
                    if r < 1 or r > q_len:
                        raise ValueError(f"extend row {i}: {r} tokens outside [1, {q_len}]")
                    if len(poss) != r:
                        raise ValueError(f"extend row {i}: positions/tokens length mismatch")
                    tok[i, :r] = np.asarray(toks, np.int32)
                    pos[i, :r] = np.asarray(poss, np.int32)
                    valid[i, :r] = True
                for i, row in enumerate(page_rows):
                    bt[i] = self.pool.padded_table(row, self.max_pages)
                span.args["context"] = int(pos.max(axis=1)[:n].sum()) + n
                self._count_page_blocks(span, pos[:, 0], pos.max(axis=1) - pos[:, 0] + 1, q_len)
                self._count_index(span, pos[:n, 0], pos[:n].max(axis=1) - pos[:n, 0] + 1)
            ex = self._get_compiled("extend", (B, q_len))
            with RecordEvent("engine.extend.dispatch"):
                logits, state = ex(
                    self.params, jnp.asarray(tok), jnp.asarray(pos),
                    jnp.asarray(valid), jnp.asarray(bt), self.pool.device_state(),
                )
                self.pool.adopt_state(state)
            with RecordEvent("engine.extend.fetch"):
                out = np.asarray(logits[:n])
        self._mark_first_token()
        return out

    # ---- convenience: batch greedy generation through the scheduler ----
    def generate(
        self,
        prompts: Sequence[Sequence[int]],
        max_new_tokens=16,
        eos_id: Optional[int] = None,
    ) -> List[List[int]]:
        """Greedy-decode every prompt (continuous batching under the hood);
        returns the generated token ids per prompt."""
        from .scheduler import ContinuousBatchingScheduler, Request

        if isinstance(max_new_tokens, int):
            max_new_tokens = [max_new_tokens] * len(prompts)
        sched = ContinuousBatchingScheduler(self, eos_id=eos_id)
        reqs = [
            Request(rid=i, prompt=list(p), max_new_tokens=int(m))
            for i, (p, m) in enumerate(zip(prompts, max_new_tokens))
        ]
        for r in reqs:
            sched.submit(r)
        while not sched.idle():
            sched.step()
        # a preempted request folds its generated prefix into the prompt
        # (recompute-on-resume) — return the full generation, not just the
        # post-resume tail
        return [r.prompt[r.prompt_len:] + list(r.generated) for r in reqs]

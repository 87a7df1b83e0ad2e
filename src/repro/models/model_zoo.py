"""Top-level Model: init / forward / loss / prefill / decode / input_specs.

One class covers all five families; family-specific behaviour lives in the
stacks (models/transformer.py). Batches:

  decoder-only text : {"tokens": (B, S) i32, "loss_mask": (B, S) f32?}
  vlm               : + {"patches": (B, frontend_tokens, frontend_dim)}
                      text length = S - frontend_tokens (patches prepended,
                      total sequence == the assigned cell seq_len)
  encdec (audio)    : {"frames": (B, S/2, frontend_dim), "tokens": (B, S/2)}
                      enc + dec streams split the cell's seq_len budget

``param_specs`` returns *logical* PartitionSpecs (axis names: embed, heads,
ff, expert, vocab, data) resolved by repro.distributed.sharding.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import transformer as tfm
from repro.models.layers import apply_norm, dense_init, init_norm, norm_specs
from repro.telemetry import scopes

Params = dict[str, Any]


def _dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


@dataclasses.dataclass
class Model:
    cfg: ModelConfig

    # ------------------------------------------------------------------ init
    def init(self, key) -> Params:
        cfg = self.cfg
        dtype = _dtype(cfg)
        ks = jax.random.split(key, 6)
        p: Params = {
            "embed": dense_init(ks[0], cfg.vocab_size, cfg.d_model, dtype, scale=1.0),
            "final_norm": init_norm(ks[1], cfg.d_model, cfg.norm_type, dtype),
        }
        if cfg.num_encoder_layers > 0:
            p["enc_blocks"] = tfm.init_stack(ks[2], cfg, cfg.num_encoder_layers, dtype)
            p["enc_final_norm"] = init_norm(ks[3], cfg.d_model, cfg.norm_type, dtype)
            p["blocks"] = tfm.init_stack(ks[4], cfg, cfg.num_layers, dtype,
                                         cross_attn=True)
        else:
            p["blocks"] = tfm.init_stack(ks[4], cfg, cfg.num_layers, dtype)
        if cfg.frontend is not None:
            p["frontend_proj"] = dense_init(ks[5], cfg.frontend_dim, cfg.d_model, dtype)
        if not cfg.tie_embeddings:
            p["lm_head"] = dense_init(ks[5], cfg.d_model, cfg.vocab_size, dtype)
        return p

    def param_specs(self):
        cfg = self.cfg
        s: Params = {
            "embed": P("vocab", "embed"),
            "final_norm": norm_specs(cfg.norm_type),
        }
        if cfg.num_encoder_layers > 0:
            s["enc_blocks"] = tfm.stack_specs(cfg)
            s["enc_final_norm"] = norm_specs(cfg.norm_type)
            s["blocks"] = tfm.stack_specs(cfg, cross_attn=True)
        else:
            s["blocks"] = tfm.stack_specs(cfg)
        if cfg.frontend is not None:
            s["frontend_proj"] = P("embed", None)
        if not cfg.tie_embeddings:
            s["lm_head"] = P("embed", "vocab")
        return s

    # --------------------------------------------------------------- forward
    @scopes.scoped(scopes.LOGITS)
    def _logits(self, params, h):
        h = apply_norm(params["final_norm"], h, self.cfg.norm_type)
        head = params.get("lm_head", None)
        if head is None:
            head = params["embed"].T
        return h @ head

    def _embed_decoder_input(self, params, batch):
        cfg = self.cfg
        x = jnp.take(params["embed"], batch["tokens"], axis=0)
        if cfg.frontend == "vision":
            front = batch["patches"].astype(x.dtype) @ params["frontend_proj"]
            x = jnp.concatenate([front, x], axis=1)
        return x

    def _encode(self, params, batch, deterministic=True):
        front = batch["frames"].astype(_dtype(self.cfg)) @ params["frontend_proj"]
        h, _ = tfm.apply_stack(params["enc_blocks"], self.cfg, front,
                               deterministic=deterministic,
                               causal_override=False)
        return apply_norm(params["enc_final_norm"], h, self.cfg.norm_type)

    def forward(self, params, batch, *, deterministic: bool = True,
                dropout_seed: int = 0):
        """Returns (logits, aux_loss). ``batch["segment_ids"]`` (B, S) int32,
        when present, isolates packed documents in decoder self-attention
        and makes RoPE segment-relative (boundary-correct packed training)."""
        cfg = self.cfg
        segment_ids = batch.get("segment_ids")
        if segment_ids is not None and (cfg.frontend is not None
                                        or cfg.num_encoder_layers > 0):
            raise ValueError(
                "packed segment_ids are a text-decoder feature: frontends "
                "prepend a modality stream with its own position space, and "
                "cross-attention reads one shared encoder stream that cannot "
                "be isolated per packed document")
        enc_out = None
        if cfg.num_encoder_layers > 0:
            enc_out = self._encode(params, batch, deterministic)
        x = self._embed_decoder_input(params, batch)
        h, aux = tfm.apply_stack(params["blocks"], cfg, x, enc_out=enc_out,
                                 segment_ids=segment_ids,
                                 deterministic=deterministic,
                                 dropout_seed=dropout_seed)
        return self._logits(params, h), aux

    # ------------------------------------------------------------------ loss
    def loss(self, params, batch, *, deterministic: bool = False,
             dropout_seed: int = 0, aux_weight: float = 0.01):
        cfg = self.cfg
        logits, aux = self.forward(params, batch, deterministic=deterministic,
                                   dropout_seed=dropout_seed)
        tokens = batch["tokens"]
        n_front = cfg.frontend_tokens if cfg.frontend == "vision" else 0
        if n_front:
            logits = logits[:, n_front:]
        with scopes.named(scopes.LOSS):
            # next-token prediction
            logits = logits[:, :-1].astype(jnp.float32)
            targets = tokens[:, 1:]
            mask = batch.get("loss_mask",
                             jnp.ones_like(tokens, jnp.float32))[:, 1:]
            logz = jax.scipy.special.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, targets[..., None],
                                       axis=-1)[..., 0]
            nll = (logz - gold) * mask
            denom = jnp.maximum(mask.sum(), 1.0)
            ce = nll.sum() / denom
            total = ce + aux_weight * aux
        return total, {"loss": total, "ce": ce, "aux": aux,
                       "tokens": denom}

    # --------------------------------------------------------------- serving
    def decode_capacity(self, prompt_len: int, max_new: int) -> int:
        return prompt_len + max_new

    def init_decode_state(self, batch: int, capacity: int, *, enc_len: int = 0):
        cfg = self.cfg
        caches = tfm.init_decode_cache(cfg, batch, capacity, _dtype(cfg),
                                       enc_len=enc_len)
        return {"caches": caches, "kv_len": jnp.zeros((batch,), jnp.int32)}

    def prefill(self, params, batch, capacity: int):
        """Run the prompt, build decode state, return (state, last_logits)."""
        cfg = self.cfg
        enc_out = None
        if cfg.num_encoder_layers > 0:
            enc_out = self._encode(params, batch)
        x = self._embed_decoder_input(params, batch)
        h, caches = tfm.apply_stack_prefill(params["blocks"], cfg, x, capacity,
                                            enc_out=enc_out)
        logits = self._logits(params, h[:, -1:])
        state = {"caches": caches,
                 "kv_len": jnp.full((x.shape[0],), x.shape[1], jnp.int32)}
        return state, logits

    def supports_paged_decode(self) -> bool:
        """Paged decode scatters token-indexed K/V rows into shared pool
        pages; exactly the families whose per-layer state is a
        length-indexed KV cache support it (same predicate as packed
        prefill — SSM/hybrid recurrent state and encoder/frontend streams
        have no per-token rows to page)."""
        return self.supports_packed_prefill()

    def init_paged_decode_state(self, batch: int, num_pages: int,
                                page_size: int, pages_per_seq: int):
        """Decode state over a shared page pool: lane-dense pools
        (L, num_pages, page_size, hkv*hd), a (batch, pages_per_seq) page
        table (negative = unallocated), and logical lengths. The batch dim
        costs no cache memory — rows are just decode lanes; all KV bytes
        live in the pool."""
        cfg = self.cfg
        assert self.supports_paged_decode(), cfg.family
        caches = tfm.init_paged_decode_cache(cfg, num_pages, page_size,
                                             _dtype(cfg))
        return {"caches": caches,
                "page_table": jnp.full((batch, pages_per_seq), -1, jnp.int32),
                "kv_len": jnp.zeros((batch,), jnp.int32)}

    def paged_decode_state_specs(self):
        """Logical PartitionSpecs for the paged decode state — the sharded
        analogue of the dense ``decode_cache_specs`` path in
        ``input_specs``: the pool's page dim shards like the dense capacity
        dim ("kv_seq"), page table and lengths follow the batch lanes."""
        return {"caches": tfm.paged_decode_cache_specs(),
                "page_table": P("data", None),
                "kv_len": P("data")}

    def supports_packed_prefill(self) -> bool:
        """Packed prefill scatters per-segment KV-cache row ranges into
        slots; that requires every cache leaf to be a (length-indexed) KV
        cache. SSM/hybrid states and encoder/frontend streams don't split
        per segment."""
        cfg = self.cfg
        return (cfg.family in ("dense", "moe") and not cfg.hybrid
                and cfg.num_encoder_layers == 0 and cfg.frontend is None)

    def prefill_packed(self, params, batch):
        """Prefill SEVERAL requests packed into one (1, ΣLᵢ) sequence.

        batch: {"tokens": (1, S), "segment_ids": (1, S)} where segment i
        occupies a contiguous token run (pad tail uses a sentinel id).
        Segment masking + segment-relative RoPE make each request's hidden
        states and K/V rows identical to a batch-1 prefill of that request
        alone. Returns (caches, logits (1, S, V)): the caller gathers each
        segment's last-token logits and scatters its K/V row range into a
        decode slot (serve/engine.py).
        """
        cfg = self.cfg
        assert self.supports_packed_prefill(), cfg.family
        seg = batch["segment_ids"]
        x = self._embed_decoder_input(params, batch)
        h, caches = tfm.apply_stack_prefill(
            params["blocks"], cfg, x, x.shape[1], segment_ids=seg)
        return caches, self._logits(params, h)

    def prefill_chunk_paged(self, params, batch, caches):
        """Prefill the NEXT chunk of several sequences, packed, against the
        shared page pool (the chunked-prefill model step, DESIGN.md §10).

        batch: {"tokens": (1, S), "q_segment_ids": (1, S),
                "q_positions": (1, S)  — logical positions hist_i + r,
                "kv_segment_ids"/"kv_positions": (1, Sk) for the in-place
                prefixes, "dest_page"/"dest_off": (S,) scatter destinations,
                "page_list": (1, Sk // page_size) kv-side page indices}.
        ``caches`` is the engine's paged pool pytree (donated by the jit).
        Each layer scatters the chunk's K/V rows into the pool, then
        attends the segment's full logical prefix IN PLACE through
        ``page_list`` with the traced per-segment q_offset — so every
        chunk is exact attention over all prior KV with zero gather
        copies, and the pool after the final chunk is identical to an
        atomic prefill's. Returns (new_caches, logits (1, S, V)): the
        caller samples each finishing segment's last-token logits.
        """
        cfg = self.cfg
        assert self.supports_paged_decode(), cfg.family
        x = jnp.take(params["embed"], batch["tokens"], axis=0)
        h, caches = tfm.apply_stack_chunk_prefill(
            params["blocks"], cfg, x, caches,
            batch["dest_page"], batch["dest_off"], batch["page_list"],
            batch["q_segment_ids"], batch["kv_segment_ids"],
            batch["q_positions"], batch["kv_positions"])
        return caches, self._logits(params, h)

    def decode_step(self, params, state, token):
        """token: (B,) i32. Returns (new_state, logits (B, 1, V)).

        Dispatches on the state's pytree structure: a ``page_table`` key
        selects the paged KV-cache path (serve/kv_cache.py), otherwise the
        dense per-slot cache. One jit trace per engine either way.
        """
        cfg = self.cfg
        x = jnp.take(params["embed"], token[:, None], axis=0)
        if "page_table" in state:
            h, caches = tfm.apply_stack_decode_paged(
                params["blocks"], cfg, x, state["caches"],
                state["page_table"], state["kv_len"])
            logits = self._logits(params, h)
            new_state = {"caches": caches, "page_table": state["page_table"],
                         "kv_len": state["kv_len"] + 1}
            return new_state, logits
        h, caches = tfm.apply_stack_decode(params["blocks"], cfg, x,
                                           state["caches"], state["kv_len"])
        logits = self._logits(params, h)
        new_state = {"caches": caches, "kv_len": state["kv_len"] + 1}
        return new_state, logits

    # ----------------------------------------------------- dry-run interface
    def input_specs(self, shape: ShapeConfig):
        """ShapeDtypeStructs + logical data shardings for one cell.

        train/prefill: the batch pytree. decode: (state, token).
        """
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        data = ("data",)

        def tok(b, s):
            return jax.ShapeDtypeStruct((b, s), jnp.int32)

        if shape.kind in ("train", "prefill"):
            if cfg.num_encoder_layers > 0:
                half = S // 2
                batch = {"frames": jax.ShapeDtypeStruct(
                            (B, half, cfg.frontend_dim), jnp.float32),
                         "tokens": tok(B, half)}
                specs = {"frames": P(data, None, None), "tokens": P(data, None)}
            elif cfg.frontend == "vision":
                nf = cfg.frontend_tokens
                batch = {"patches": jax.ShapeDtypeStruct(
                            (B, nf, cfg.frontend_dim), jnp.float32),
                         "tokens": tok(B, S - nf)}
                specs = {"patches": P(data, None, None), "tokens": P(data, None)}
            else:
                batch = {"tokens": tok(B, S)}
                specs = {"tokens": P(data, None)}
                if shape.kind == "train":
                    # packed-document ids from the data pipeline (§7.5)
                    batch["segment_ids"] = tok(B, S)
                    specs["segment_ids"] = P(data, None)
            if shape.kind == "train":
                batch["loss_mask"] = jax.ShapeDtypeStruct((B, *batch["tokens"].shape[1:]),
                                                          jnp.float32)
                specs["loss_mask"] = P(data, None)
            return batch, specs

        # decode: state + one token
        capacity = S if cfg.num_encoder_layers == 0 else S // 2
        enc_len = S // 2 if cfg.num_encoder_layers > 0 else 0
        state_shapes = jax.eval_shape(
            lambda: self.init_decode_state(B, capacity, enc_len=enc_len))
        state_specs = {
            "caches": tfm.decode_cache_specs(cfg, enc=cfg.num_encoder_layers > 0),
            "kv_len": P(data),
        }
        token = jax.ShapeDtypeStruct((B,), jnp.int32)
        return (state_shapes, token), (state_specs, P(data))


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)

"""Model-based OPs — data processing WITH foundation models (paper §3).

``lm_perplexity_filter`` scores samples with a real JAX LM from the model
substrate (jit-compiled batched scoring on whatever devices/mesh are
available) — the first-class integration between the Data-Juicer runtime
and the training stack. ``ngram_perplexity_filter`` is the cheap rule-based
counterpart (fit on the corpus itself), mirroring the paper's observation
that model-based scoring complements rule-based scoring.
"""
from __future__ import annotations

import math
from typing import List, Optional

import numpy as np

from repro.core import obs
from repro.core.ops_base import Filter, Mapper, shared_words
from repro.core.registry import register
from repro.ops.text_filters import _RangeFilter


@register("ngram_perplexity_filter")
class NgramPerplexityFilter(_RangeFilter):
    """Bigram perplexity under a model fit on the probe corpus (rule-based
    quality proxy; high ppl = unusual/noisy text)."""

    stat_key = "ngram_ppl"

    def __init__(self, min_val=0.0, max_val=math.inf, vocab: int = 1 << 15, **kw):
        super().__init__(min_val=min_val, max_val=max_val, **kw)
        self.vocab = vocab
        self._uni: Optional[np.ndarray] = None
        self._bi: Optional[dict] = None

    def _ids(self, text: str) -> List[int]:
        import hashlib

        return [
            int.from_bytes(hashlib.blake2b(w.lower().encode(), digest_size=4).digest(), "little")
            % self.vocab
            for w in text.split()
        ]

    def _ids_sample(self, s) -> List[int]:
        import hashlib

        return [
            int.from_bytes(hashlib.blake2b(w.lower().encode(), digest_size=4).digest(), "little")
            % self.vocab
            for w in shared_words(s)
        ]

    def fit(self, texts: List[str]) -> None:
        uni = np.ones(self.vocab, np.float64)  # add-one smoothing
        bi: dict = {}
        for t in texts:
            ids = self._ids(t)
            for a in ids:
                uni[a] += 1
            for a, b in zip(ids, ids[1:]):
                bi[(a, b)] = bi.get((a, b), 0) + 1
        self._uni, self._bi = uni / uni.sum(), bi

    def setup(self):
        if self._uni is None:
            self._uni = np.full(self.vocab, 1.0 / self.vocab)
            self._bi = {}

    def _stat(self, s):
        self.setup()
        ids = self._ids_sample(s)
        if len(ids) < 2:
            return 0.0
        logp = 0.0
        for a, b in zip(ids, ids[1:]):
            c_ab = self._bi.get((a, b), 0)
            c_a = self._uni[a] * self.vocab  # un-normalised-ish
            p = (c_ab + 0.5) / (c_a + 0.5 * self.vocab)
            logp += math.log(max(p, 1e-12))
        return float(math.exp(-logp / (len(ids) - 1)))


def batch_scorer(model):
    """Jitted ``(params, tokens, labels, mask) -> (B,)`` per-row mean losses:
    ONE call scores the whole batch (vmap of the model's batch-of-one
    ``loss_fn``). Built from the model alone, so the step can be lowered and
    compiled from abstract params (``jax.eval_shape``) without allocating
    them."""
    import jax

    def score_one(params, tokens, labels, mask):
        loss, _ = model.loss_fn(
            params,
            {"tokens": tokens[None], "labels": labels[None], "loss_mask": mask[None]},
        )
        return loss

    return jax.jit(jax.vmap(score_one, in_axes=(None, 0, 0, 0)))


def encode_rows(tok, texts: List[str], seq: int):
    """Host-side next-token batch for the scorer: ``(tokens, labels, mask)``,
    each ``(len(texts), seq)``; ``mask`` is 1.0 on the scored positions.
    The target of each scored position is the id that follows it: the next
    word, EOS after the last, or the first word past the cut."""
    toks = np.zeros((len(texts), seq), np.int32)
    labels = np.zeros((len(texts), seq), np.int32)
    mask = np.zeros((len(texts), seq), np.float32)
    for i, t in enumerate(texts):
        ids = tok.encode(t)[: seq + 1]
        n = len(ids) - 1
        toks[i, :n] = ids[:n]
        labels[i, :n] = ids[1:]
        mask[i, :n] = 1.0
    return toks, labels, mask


@register("lm_perplexity_filter")
class LMPerplexityFilter(_RangeFilter):
    """Perplexity from a JAX LM (model substrate), batched + jit'd.

    ``arch`` picks any assigned architecture (reduced config by default so
    the OP runs on CPU); ``params_path`` can point at a trained checkpoint
    (e.g. produced by examples/train_e2e.py — data-model co-development).
    A chunked SSM scores ``seq_len`` rounded up to a multiple of its chunk,
    the only lengths its chunked scan accepts.
    """

    stat_key = "lm_ppl"
    uses_model = True
    gpu_mem_required = 4 << 30
    default_batch_size = 64

    def __init__(self, min_val=0.0, max_val=math.inf, arch: str = "mamba2-1.3b",
                 reduced: bool = True, params_path: str = "", seq_len: int = 128, **kw):
        super().__init__(min_val=min_val, max_val=max_val, **kw)
        self.params.update(arch=arch, reduced=reduced, params_path=params_path,
                           seq_len=seq_len)
        self._model = None
        self._params = None
        self._tok = None
        self._score_batch = None

    def setup(self):
        if self._model is not None:
            return
        import jax

        from repro.configs import get_config
        from repro.data.tokenizer import HashWordTokenizer
        from repro.models.model_zoo import build_model

        cfg = get_config(self.params["arch"], reduced=self.params["reduced"])
        self._model = build_model(cfg, remat_policy="none")
        if self.params["params_path"]:
            from repro.train.checkpointing import load_params

            self._params = load_params(self.params["params_path"])
        else:
            self._params = self._model.init_params(jax.random.PRNGKey(0))
        self._tok = HashWordTokenizer(cfg.vocab_size)
        chunk = cfg.ssm_chunk if cfg.family == "ssm" else 1
        self._seq = -(-self.params["seq_len"] // chunk) * chunk
        self._score_batch = batch_scorer(self._model)

    def _ppl_batch(self, texts: List[str]) -> np.ndarray:
        self.setup()
        import jax.numpy as jnp

        with obs.timed("scorer.encode"):
            toks, labels, mask = encode_rows(self._tok, texts, self._seq)
            scored = float(mask.sum())
            # pad the batch dim to a multiple of 64 to bound jit retraces
            # without over-scoring (pow2 padding cost up to +33% work on odd
            # batch sizes)
            n = len(texts)
            nb = max(64, ((n + 63) // 64) * 64)
            if nb != n:
                toks = np.pad(toks, ((0, nb - n), (0, 0)))
                labels = np.pad(labels, ((0, nb - n), (0, 0)))
                mask = np.pad(mask, ((0, nb - n), (0, 0)))
                mask[n:, 0] = 1.0  # avoid 0/0 in padded rows
        with obs.timed("scorer.step", {"scorer.tokens_scored": scored,
                                       "scorer.tokens_padded": toks.size - scored}):
            losses = self._score_batch(
                self._params, jnp.asarray(toks), jnp.asarray(labels), jnp.asarray(mask)
            )
            losses = np.asarray(losses, np.float64)[:n]
        return np.exp(np.minimum(losses, 30.0))

    def process_batch(self, batch):
        self.setup()
        out = []
        # self-chunk at the accelerator-friendly batch size regardless of the
        # caller's batching (keeps the logits working set bounded)
        for i in range(0, len(batch), self.default_batch_size):
            chunk = batch[i : i + self.default_batch_size]
            ppls = self._ppl_batch([s.get("text", "") for s in chunk])
            for s, p in zip(chunk, ppls):
                s.setdefault("stats", {})[self.stat_key] = float(p)
                if self.min_val <= p <= self.max_val:
                    out.append(s)
        return out

    def _stat(self, s):  # pragma: no cover — batch path is used
        return float(self._ppl_batch([s.get("text", "")])[0])


@register("quality_score_filter")
class QualityScoreFilter(_RangeFilter):
    """Composite quality score from rule stats (logistic blend) — the
    rule-based counterpart of llm_quality_score_filter."""

    stat_key = "quality_score"
    text_only_stat = True  # _stat reads only sample["text"] -> columnar-safe

    def _stat(self, s):
        t = s.get("text", "")
        if not t:
            return 0.0
        words = t.split()
        n_words = len(words)
        alnum = sum(c.isalnum() or c.isspace() for c in t) / len(t)
        avg_wl = np.mean([len(w) for w in words]) if words else 0.0
        rep = 0.0
        if n_words >= 3:
            grams = [tuple(words[i : i + 3]) for i in range(n_words - 2)]
            rep = 1.0 - len(set(grams)) / len(grams)
        z = (
            1.5 * (alnum - 0.7) + 0.8 * math.tanh(n_words / 100.0)
            - 2.0 * rep + 0.3 * math.tanh((avg_wl - 2.0) / 4.0)
        )
        return float(1.0 / (1.0 + math.exp(-3.0 * z)))

    def _stat_values(self, block) -> np.ndarray:
        """Columnar path: the alnum term — the bulk of ``_stat``'s per-char
        work — comes off the buffer via the byte-class tables (exact on
        ASCII rows, per-char recompute otherwise); word splitting and the
        trigram-repetition term stay per row. Every term reproduces the row
        path bit-for-bit: integer counts divide identically, and the mean
        word length is an exact small-integer sum either way."""
        from repro.core.columnar import ascii_alnum_space_counts, ascii_rows_mask

        col = block.str_column("text")  # TypeError on non-str -> row fallback
        if col is None:
            return np.zeros(len(block), np.float64)
        offs, buf = col
        texts = block.string_values("text")
        ok = ascii_rows_mask(offs, buf).tolist()
        lens_b = (offs[1:] - offs[:-1]).tolist()
        acnt = ascii_alnum_space_counts(offs, buf).tolist()
        out = np.empty(len(texts), np.float64)
        for i, t in enumerate(texts):
            if not t:
                out[i] = 0.0
                continue
            words = t.split()
            n_words = len(words)
            alnum = (acnt[i] / lens_b[i] if ok[i]
                     else sum(c.isalnum() or c.isspace() for c in t) / len(t))
            # exact np.mean([len(w)...]) equivalent: an integer sum below
            # 2**53 divides identically
            avg_wl = float(sum(map(len, words))) / n_words if words else 0.0
            rep = 0.0
            if n_words >= 3:
                # same trigram tuples as the row path's slice loop
                rep = 1.0 - len(set(zip(words, words[1:], words[2:]))) \
                    / (n_words - 2)
            z = (
                1.5 * (alnum - 0.7) + 0.8 * math.tanh(n_words / 100.0)
                - 2.0 * rep + 0.3 * math.tanh((avg_wl - 2.0) / 4.0)
            )
            out[i] = 1.0 / (1.0 + math.exp(-3.0 * z))
        return out


@register("image_captioning_mapper")
class ImageCaptioningMapper(Mapper):
    """Synthesis: generates captions from image tags (offline stand-in for
    the BLIP-2 captioner; preserves token-aligned output schema)."""

    uses_model = True
    gpu_mem_required = 16 << 30

    def process_single(self, s):
        from repro.core import schema as S

        metas = s.get("image_meta", []) or []
        if not metas:
            return s
        caps = []
        for m in metas:
            tags = m.get("tags", [])
            caps.append(
                f"{S.IMAGE_TOKEN} a photo of " + (", ".join(tags) if tags else "something")
            )
        s = dict(s)
        s["text"] = (" " + S.EOC + " ").join(caps)
        return s

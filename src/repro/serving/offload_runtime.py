"""The paper's runtime: offloaded serving with hybrid heterogeneous
parallelism (HeteGen §4).

Weights live in host memory.  Each linear module executes under the
scheduler's placement plan (resident / hetegen-split / streamed) through
:class:`repro.core.engine.HeteGenEngine`; everything else (norms, rope,
attention core, softmax, sampling) runs on the device.  The forward is
eager per layer — exactly how offloading runtimes execute, since weights
arrive layer by layer.

The decoder math itself is NOT defined here: the offload path executes the
same shared layer functions as the resident path
(:func:`repro.models.model.decoder_layer` via
:class:`repro.serving.backends.HeteGenBackend`), differing only in the
injected linear backend.  The placement plan is tuned for the *real*
decode batch size — §4.1's cost model shifts the optimal alpha with
compute intensity — and sampling is pluggable via
:class:`repro.serving.sampling.SamplerConfig`.

Supports the dense GQA decoder families (the paper's OPT models and
mistral-style configs).  Correctness: outputs match the fully-resident
jitted path to fp tolerance (tests/test_offload_runtime.py).

For request-level serving (per-request sampling, streaming, continuous
batching) drive the backend through :class:`repro.serving.api.LLM`
instead — this generator is the phase-aware one-shot executor kept for
stats-rich offload benchmarking (docs/SERVING.md).
"""

from __future__ import annotations

import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hw import HardwareSpec
from repro.serving.backends import HeteGenBackend, enumerate_linears  # noqa: F401  (re-export)
from repro.models.config import ModelConfig
from repro.serving.sampling import SamplerConfig, make_sampler


class OffloadGenerator:
    """HeteGen-scheduled offloaded generation for dense GQA decoders.

    ``batch`` sizes the initial placement plan; by default the plan is
    re-tuned automatically when :meth:`generate` is called with a different
    batch size (``auto_retune=False`` pins the constructed plan).
    """

    def __init__(self, cfg: ModelConfig, params: Dict, *,
                 hw: Optional[HardwareSpec] = None,
                 budget_bytes: Optional[float] = None,
                 use_alpha_benchmark: bool = True,
                 use_module_scheduler: bool = True,
                 alpha_override: Optional[float] = None,
                 batch: int = 1,
                 sampler: SamplerConfig = SamplerConfig(),
                 auto_retune: bool = True):
        self.cfg = cfg
        self.backend = HeteGenBackend(
            cfg, params, hw=hw, budget_bytes=budget_bytes, batch=batch,
            use_alpha_benchmark=use_alpha_benchmark,
            use_module_scheduler=use_module_scheduler,
            alpha_override=alpha_override)
        self.sample = make_sampler(sampler)
        self.auto_retune = auto_retune

    @property
    def policy(self):
        return self.backend.policy

    @property
    def engine(self):
        return self.backend.engine

    # ------------------------------------------------------------------
    def generate(self, tokens: np.ndarray, max_new_tokens: int,
                 *, max_len: Optional[int] = None, seed: int = 0) -> Dict:
        b, s = tokens.shape
        if self.auto_retune:
            self.backend.retune(b)
        total = max_len or (s + max_new_tokens)
        cache = self.backend.init_cache(b, total)
        self.backend.reset_stats()
        t0 = time.perf_counter()
        cache, logits = self.backend.prefill(
            {"tokens": jnp.asarray(tokens)}, cache)
        # lint: allow[prng-discipline] the benchmark runtime's seed key;
        # serving paths derive request-owned keys via sampling.request_key
        key = jax.random.PRNGKey(seed)
        tok = self.sample(logits, key)
        jax.block_until_ready(tok)
        t1 = time.perf_counter()
        out = [tok]
        for i in range(max_new_tokens - 1):
            key = jax.random.fold_in(key, i)
            cache, logits = self.backend.decode(out[-1], cache)
            out.append(self.sample(logits, key))
        jax.block_until_ready(out[-1])
        t2 = time.perf_counter()
        # stream stats aggregate over the backend's phase engines (the
        # prefill partition ran the prompt, the decode partition the loop)
        stats = self.backend.finish_stats()
        prefill_policy = self.backend.policies.get("prefill")
        return {
            "tokens": np.stack([np.asarray(t) for t in out], axis=1),
            "prefill_s": t1 - t0,
            "decode_s": t2 - t1,
            "tokens_per_s": b * max(max_new_tokens - 1, 1) / max(t2 - t1, 1e-9),
            "stream_stats": stats,
            "alpha": self.policy.alpha,
            "prefill_alpha": (None if prefill_policy is None
                              else prefill_policy.alpha),
            "batch": self.backend.batch,
            "resident_bytes": self.backend.device_resident_bytes(),
            "pinned_overhead_bytes": self.backend.pinned_overhead_bytes(),
        }

    def close(self):
        self.backend.close()

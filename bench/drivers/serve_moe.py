"""Driver of bounded-KV serving of a DeepSeek-V3-block model on one chip
of an expert-parallel group: ``ServeEngine`` in paged mode over the
latent pool, the expert layer holding this chip's share of the routed
experts, fed by one closed-loop client (``bench/drivers/serve.py``'s
window, check and control).

Beyond that driver's record, the window record holds the engine's expert
counters over the window (``moe_prefill_held_pairs``,
``moe_prefill_experts_hit``, ``moe_decode_held_pairs``,
``moe_decode_experts_hit``: ``ServeEngine.telemetry()``'s ``moe/...``).
The check runs ``bench/refs/mla_moe.py`` and reads the mean gap and a
per-request tail of the gaps (``Run.check``).
"""

from __future__ import annotations

import time

import numpy as np

from bench import moe_sizes, traffic, weights
from bench.drivers import serve
from bench.refs.mla_moe import MlaMoe

def position_gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """(B, G) gaps by which each token's logit lies below the best logit
    at its position."""
    best = ref_logits.max(-1)
    return best - np.take_along_axis(ref_logits, tokens[..., None], -1)[..., 0]


#: the quantile of a request's gaps that ``request_tail`` reads
TAIL_Q = 0.5


def request_tail(gaps: np.ndarray) -> float:
    """The widest, over the sampled requests, of a request's ``TAIL_Q``
    quantile of its (B, G) position gaps: a fault in half of one
    request's positions reads here undiluted by the other requests, while
    routing flips at isolated positions (``check``) leave it at 0."""
    return float(np.quantile(gaps, TAIL_Q, axis=1).max())


_COUNTERS = ("prefill/held_pairs", "prefill/experts_hit",
             "decode/held_pairs", "decode/experts_hit")


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file: the source
    config's keys at its top level, the deployment's held experts under
    ``expert_parallel``."""
    from repro.configs.base import ModelConfig

    m, s = moe_sizes.sizes(cfg), cfg["serving"]
    if cfg["scoring_func"] != "sigmoid" or cfg["topk_method"] != "noaux_tc" \
            or not cfg["norm_topk_prob"] or cfg["n_group"] != 1 \
            or cfg["topk_group"] != 1:
        raise ValueError("the program routes sigmoid / noaux_tc over one "
                         "group, the chosen scores normalised, only")
    n_moe = m["n_layers"] - m["n_dense"]
    return ModelConfig(
        name=cfg["name"], family="moe", n_layers=m["n_layers"],
        d_model=m["d_model"], n_heads=m["n_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=m["d_ff"],
        vocab=m["vocab"], rope_theta=float(m["rope_theta"]),
        norm_eps=m["norm_eps"], tie_embeddings=cfg["tie_word_embeddings"],
        lead=("mla",) * m["n_dense"], pattern=("mla_moe",), n_repeats=n_moe,
        kv_lora_rank=m["kv_lora_rank"], qk_nope_head_dim=m["nope"],
        qk_rope_head_dim=m["rope"], v_head_dim=m["v"],
        n_experts=m["n_experts"], top_k=m["top_k"],
        router_scoring="sigmoid", routed_scaling=m["routed_scaling"], expert_ff=m["expert_ff"],
        shared_ff=m["shared_ff"], experts_held=m["experts_held"],
        expert_offset=m["expert_offset"], page_size=s["page_size"],
        bounded_kv_pages=s["pool_pages"], kv_policy=s["kv_policy"],
        dtype=cfg["dtype"], param_dtype=cfg["dtype"])


class Run(serve.Run):
    def __init__(self, cfg: dict, mix: dict, seed: int, rehearse: bool):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.m = moe_sizes.sizes(cfg)
        self.serving = cfg["serving"]

    def _params(self):
        from repro.models import model as M

        return weights.make(M.abstract_params(self.model_cfg), self.seed,
                            self.cfg["initializer_range"])

    def setup(self, warm: bool = True) -> None:
        import jax

        from repro.serve.engine import ServeEngine

        self.model_cfg = model_config(self.cfg)
        t0 = time.perf_counter()
        self.params = self._params()
        jax.block_until_ready(self.params)
        self.setup_parts = {"weights_s": time.perf_counter() - t0}
        self.batches = traffic.make_batches(self.mix, self.seed,
                                            self.m["vocab"])
        B, S = self.batches[0].shape
        self.new = self.mix["new_tokens"]
        self.engine = ServeEngine(self.model_cfg, self.params,
                                  max_len=S + self.new, kv_mode="paged",
                                  fused=self.serving["fused"])
        if warm:
            t0 = time.perf_counter()
            self._generate(self.batches[-1])  # warm-up: the one extra batch
            self.setup_parts["warmup_s"] = time.perf_counter() - t0

    def _moe(self) -> dict:
        tel = self.engine.telemetry()
        return {k: int(tel[f"moe/{k}"]) for k in _COUNTERS}

    def window(self, seconds: float, max_calls=None) -> dict:
        before = self._moe()
        record = super().window(seconds, max_calls)
        after = self._moe()
        record.update({"moe_" + k.replace("/", "_"): after[k] - before[k]
                       for k in _COUNTERS})
        return record

    def release(self) -> None:
        import jax

        # a dropped engine is not freed (PERF.md §7), and the reference's
        # own copy of the weights needs their 6.7 GB: free them by hand
        for leaf in jax.tree.leaves(self.params):
            leaf.delete()
        super().release()

    def check(self) -> list:
        """The gap by which the served token's logit lies below the
        reference's best, read at every served position of the sampled
        requests: its mean, and its ``request_tail`` (``serve.py``'s
        check takes the widest).  Under bf16 a token's top-6 flips where
        the reference's 6th and 7th choices lie within the rounding of
        the residual stream (a margin of 1e-4 to 2e-3 in some layer at
        every position): an isolated position then reads a gap up to the
        float8 control's, while arithmetic wrong anywhere on the path
        moves most positions, and a fault in half of one request's
        positions moves the tail.  The widest gap goes to the record
        (``logit_gap_max``)."""
        prompts, tokens = self._sample()
        logits, ref_evictions = self._reference(self.cfg["precision"],
                                                prompts, tokens)
        self.ref_logits = logits
        gaps = position_gaps(logits, tokens)
        self.record["logit_gap_max"] = float(gaps.max())
        per_batch = ref_evictions // len(prompts) * self.record["batch"]
        off = abs(self.record["evictions"]
                  - per_batch * self.record["batches"])
        lim = self.cfg["limits"]
        return [("logit_gap_mean", float(gaps.mean()),
                 lim["logit_gap_mean"]),
                ("logit_gap_tail", request_tail(gaps), lim["logit_gap_tail"]),
                ("evictions_off", off, lim["evictions_off"])]

    def control(self) -> dict:
        """The float8 reference's first choices read as ``check`` reads
        the served tokens, after ``check``."""
        prompts, tokens = self._sample()
        low, _ = self._reference("float8", prompts, tokens)
        gaps = position_gaps(self.ref_logits, low.argmax(-1))
        return {"control_gap_mean": float(gaps.mean()),
                "control_gap_tail": request_tail(gaps),
                "control_gap_max": float(gaps.max())}

    def _reference(self, precision: str, prompts, tokens):
        import jax

        params = self._params()
        try:
            return MlaMoe(self.m, params, precision).logits(
                prompts, tokens, self.serving["pool_pages"],
                self.serving["page_size"])
        finally:  # the reference object outlives the call in a cycle
            for leaf in jax.tree.leaves(params):
                leaf.delete()

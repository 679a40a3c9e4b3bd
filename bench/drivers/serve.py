"""Driver of bounded-KV serving: ``repro.serve.engine.ServeEngine`` in
paged mode, as a serving operator runs it, fed by one closed-loop client.

The client submits a batch of requests, waits for ``generate`` to return
them, and submits the next; prompts are pre-generated, distinct per batch.
Weights are random, made on the device from the seed.  The check takes
requests drawn from the seed out of the last batch, runs the plain
float32 reference of the same bounded-KV semantics over each prompt and
the tokens served for it, and reads the widest gap by which a served
token's logit lies below the reference's best.  The pool's evictions,
counted by the engine, are held to the count the reference's pool makes.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench import harness, traffic, weights
from bench.peaks import decoder_sizes
from bench.refs.decoder import Decoder, widest_gap

#: source config key -> the program's ModelConfig field
_FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}


class Run:
    def __init__(self, cfg: dict, mix: dict, seed: int, rehearse: bool):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.m = decoder_sizes(cfg)
        self.serving = cfg["serving"]

    def _params(self):
        from repro.models import model as M

        return weights.make(M.abstract_params(self.model_cfg), self.seed,
                            self.cfg["config"]["initializer_range"])

    def setup(self, warm: bool = True) -> None:
        import jax

        from repro.configs.base import ModelConfig
        from repro.serve.engine import ServeEngine

        c, s = self.cfg["config"], self.serving
        self.model_cfg = ModelConfig(
            name=self.cfg["name"], family="dense",
            head_dim=self.m["head_dim"],
            page_size=s["page_size"], bounded_kv_pages=s["pool_pages"],
            kv_policy=s["kv_policy"], dtype=c["torch_dtype"],
            param_dtype=c["torch_dtype"],
            **{f: c[k] for k, f in _FIELDS.items()})
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
                                  fused=s["fused"])
        if warm:
            t0 = time.perf_counter()
            self._generate(self.batches[-1])  # warm-up: the one extra batch
            self.setup_parts["warmup_s"] = time.perf_counter() - t0

    def _generate(self, prompts) -> list:
        from repro.serve.engine import Request

        res = self.engine.generate([
            Request(i, p.tolist(), max_new_tokens=self.new, temperature=0.0)
            for i, p in enumerate(prompts)])
        return [res[i].tokens for i in range(len(prompts))]

    def _evictions(self) -> int:
        return int(self.engine.telemetry()["kv/pool/evictions"])

    def _compiles(self) -> int:
        return (self.engine._prefill.sentinel.traces
                + self.engine._loop_sentinel.traces)

    def window(self, seconds: float, max_calls=None) -> dict:
        ev0, comp0 = self._evictions(), self._compiles()
        batches = tokens = failed = 0
        t0, each = time.perf_counter(), []
        while True:
            b = batches % self.mix["batches"]
            with harness.annotate("bench/generate"):
                served = self._generate(self.batches[b])
            batches += 1
            each.append(time.perf_counter() - t0 - sum(each))
            tokens += sum(len(t) for t in served)
            failed += sum(len(t) != self.new for t in served)
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds or (max_calls and batches >= max_calls):
                break
        self.last = (b, served)
        B, S = self.batches[0].shape
        self.record = {
            "batches": batches, "elapsed_s": elapsed, "tokens": tokens,
            "batch": B, "prompt_len": S, "new_tokens": self.new,
            "prefills": batches, "decode_steps": batches * (self.new - 1),
            "evictions": self._evictions() - ev0,
            "compiles_in_window": self._compiles() - comp0,
            "call_s_min": min(each), "call_s_max": max(each),
            "pool_keys": (self.serving["pool_pages"]
                          * self.serving["page_size"]),
            "attempted": batches * B, "failed": failed, **self.setup_parts}
        return self.record

    def end_to_end(self, record: dict) -> dict:
        return {"serve_tok_s": record["tokens"] / record["elapsed_s"]}

    def release(self) -> None:
        del self.engine, self.params
        gc.collect()

    def _sample(self):
        """Prompts and served tokens of requests of the last batch, drawn
        from the seed."""
        b, served = self.last
        prompts = self.batches[b]
        pick = np.sort(traffic.stream(self.seed, 2**31 - 1).choice(
            len(prompts), self.mix["check_requests"], replace=False))
        return prompts[pick], np.array([served[i] for i in pick])

    def _reference(self, precision: str, prompts, tokens):
        return Decoder(self.m, self._params(), precision).logits(
            prompts, tokens, self.serving["pool_pages"],
            self.serving["page_size"])

    def check(self) -> list:
        prompts, tokens = self._sample()
        logits, ref_evictions = self._reference(self.cfg["precision"],
                                                prompts, tokens)
        self.ref_logits = logits
        per_batch = ref_evictions // len(prompts) * self.record["batch"]
        off = abs(self.record["evictions"]
                  - per_batch * self.record["batches"])
        lim = self.cfg["limits"]
        return [("logit_gap", widest_gap(logits, tokens), lim["logit_gap"]),
                ("evictions_off", off, lim["evictions_off"])]

    def control(self) -> dict:
        """The control's reading, after ``check``: at each served position,
        the gap under the reference of the token that the float8
        reference puts first."""
        prompts, tokens = self._sample()
        low, _ = self._reference("float8", prompts, tokens)
        return {"control_gap": widest_gap(self.ref_logits, low.argmax(-1))}

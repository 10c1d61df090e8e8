"""Driver: a decoder LM behind the continuous-batching ``ContinuousLMEngine``.

Stands the model up the way a user does (``repro.api.build`` ->
``compile`` -> ``ContinuousLMEngine`` over the compiled plan), with the
benchmark's own weights, and drives ``submit``/``pump`` with the traffic:

* closed loop: ``clients`` callers; set-up submits each one's first
  request and pumps once, which admits and prefills them, so the window
  opens on full slots; every finished request is followed at once by
  that caller's next;
* open loop: Poisson arrivals, each charged from its scheduled time.

A token is *delivered* when the ``pump()`` call that put it into the
slot's host-side state returns; the engine keeps no per-token timestamp,
so the driver wraps three methods of this engine instance (the program's
code is unchanged):

* ``_dispatch``: one model step; its host time is the ``prefill_chunk``
  span for a ``(1, chunk)`` call and the ``decode_step`` span for a
  ``(slots, 1)`` call, with the live context of every row;
* ``_prefill``: the admitted slot's first token, stamped on return;
* ``_decode_step``: one token for every live slot, stamped on return.

The ``_dispatch`` wrapper also starts the profiler on time inside a
``pump()`` that runs tens of prefill chunks.  In a traced run the
engine's own ``lm.*`` spans join the harness's.
"""
from __future__ import annotations

import collections
import time

import numpy as np

from .common import Run, record_engine_spans, seed32, span, timed
from .traffic import BLOCK, lm_requests, open_schedule


def arch_config(config: dict):
    """The program's ArchConfig for a configuration file's model keys."""
    import dataclasses

    from repro.configs.base import ArchConfig
    from repro.core.quant import PAPER_CONFIGS

    m, q = config, config["quant"]
    if not m.get("tie_word_embeddings", False):
        raise ValueError("the lm_continuous driver serves tied embeddings")
    quant = dataclasses.replace(PAPER_CONFIGS[q["name"]], w_bits=q["w_bits"],
                                a_bits=q["a_bits"])
    return ArchConfig(
        name=config["name"], family="dense",
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], d_ff=m["intermediate_size"],
        vocab=m["vocab_size"], head_dim=m["head_dim"],
        rope_theta=float(m["rope_theta"]), tie_embeddings=True,
        pattern=("attn",), act="swiglu", quant=quant)


def program_params(p: dict) -> dict:
    """The benchmark's stacked weights in the program's parameter tree."""
    attn = {k: p[k] for k in ("wq", "wk", "wv", "wo")}
    mlp = {k: p[k] for k in ("w_in", "w_gate", "w_out")}
    return dict(embed=p["embed"], final_norm=p["final_norm"],
                blocks={"attn": {"attn": dict(attn, ln=p["ln1"]),
                                 "mlp": dict(mlp, ln=p["ln2"])}})


class _Req:
    __slots__ = ("rid", "t_arrive", "prompt", "n_out", "tokens", "times",
                 "t_admit")

    def __init__(self, rid, t_arrive, prompt, n_out):
        self.rid, self.t_arrive = rid, t_arrive
        self.prompt, self.n_out = prompt, n_out
        self.tokens, self.times = [], []
        self.t_admit = None


class System:
    def __init__(self, config: dict, traffic: dict, seed: int, run: Run,
                 reference):
        from repro import api
        from repro.launch.engine import ContinuousLMEngine

        self.config, self.traffic, self.run = config, traffic, run
        eng = config["engine"]
        cfg = arch_config(config)
        self.vocab = cfg.vocab
        params = program_params(reference.init_params(config, seed32(seed, 1)))
        compiled = api.build(cfg, params=params).compile(
            target=eng["target"], batch_hints=(int(eng["num_slots"]),),
            prompt_len=int(eng["page_size"]))
        del params
        self.engine = ContinuousLMEngine(
            None, cfg, num_slots=int(eng["num_slots"]),
            page_size=int(eng["page_size"]), num_pages=int(eng["num_pages"]),
            max_seq=int(eng["max_seq"]), model_plan=compiled.plan)
        self.rng = np.random.default_rng(seed32(seed, 2))
        self._payloads: collections.deque = collections.deque()
        self.reqs: dict[int, _Req] = {}
        self._live: dict[int, object] = {}    # rid -> the engine's slot
        self.finished = 0
        self._wrap()

    def _wrap(self) -> None:
        e, run = self.engine, self.run

        def step_info(table_rows, toks, pos, valid):
            live = [(int(p), int(v)) for p, v in zip(pos, valid) if v > 0]
            return dict(rows=int(toks.shape[0]), seq=int(toks.shape[1]),
                        q=[v for _, v in live], ctx=[p + v for p, v in live])

        dispatch = e._dispatch
        chunk = timed(run, "prefill_chunk", dispatch, info=step_info)
        step = timed(run, "decode_step", dispatch, info=step_info)

        def classify(table_rows, toks, pos, valid):
            fn = chunk if table_rows.shape[0] == 1 and toks.shape[1] > 1 else step
            return fn(table_rows, toks, pos, valid)

        prefill, decode = e._prefill, e._decode_step

        def on_prefill(slot_i, s):
            prefill(slot_i, s)
            r = self.reqs.get(s.rid)
            if r is None:                  # the warm-up request
                return
            r.t_admit = s.t_start
            self._stamp(r, s, time.perf_counter())
            if len(r.tokens) < r.n_out:
                self._live[s.rid] = s
            else:
                self.finished += 1

        def on_decode():
            decode()
            now = time.perf_counter()
            for rid, s in list(self._live.items()):
                r = self.reqs[rid]
                self._stamp(r, s, now)
                if len(r.tokens) >= r.n_out:
                    del self._live[rid]
                    self.finished += 1

        e._dispatch, e._prefill, e._decode_step = classify, on_prefill, on_decode

    @staticmethod
    def _stamp(r: _Req, s, now: float) -> None:
        new = s.emitted[len(r.tokens):]
        r.tokens.extend(int(t) for t in new)
        r.times.extend([now] * len(new))

    def warm(self) -> None:
        """Compile and run once the two step shapes and the page reset;
        a closed loop then admits and prefills its callers' first
        requests (the contexts the window decodes over)."""
        self.engine.serve([(np.arange(1, 2 * self.engine.chunk + 1,
                                      dtype=np.int32) % self.vocab, 2)])
        if self.traffic["loop"] == "closed":
            now = time.perf_counter()
            for _ in range(int(self.traffic["clients"])):
                self._submit(now)
            self.engine.pump()

    def _submit(self, t_arrive: float) -> None:
        if not self._payloads:
            self._payloads.extend(lm_requests(
                self.traffic, int(self.traffic.get("block", BLOCK)),
                self.vocab, self.rng))
        prompt, n_out = self._payloads.popleft()
        rid = self.engine.submit((prompt, n_out), t_submit=t_arrive)
        self.reqs[rid] = _Req(rid, t_arrive, prompt, n_out)

    def drive(self, t_start: float, t_end: float, tracer) -> None:
        merge = record_engine_spans(self.run, self.engine, tracer)
        if self.traffic["loop"] == "closed":
            self._closed(t_end, tracer)
        else:
            self._open(t_start, t_end, tracer)
        merge()
        for r in self.reqs.values():
            self.run.requests.append(dict(
                rid=r.rid, t_arrive=r.t_arrive, t_admit=r.t_admit,
                n_out=r.n_out, prompt_len=len(r.prompt), times=r.times))

    def _closed(self, t_end: float, tracer) -> None:
        clients = int(self.traffic["clients"])
        e = self.engine
        while True:
            now = time.perf_counter()
            if now >= t_end:
                return
            tracer.poll(now)
            with span(self.run, "submit"):
                for _ in range(clients - (len(self.reqs) - self.finished)):
                    self._submit(time.perf_counter())
            with span(self.run, "pump"):
                e.pump()

    def _open(self, t_start: float, t_end: float, tracer) -> None:
        sched = open_schedule(self.traffic, t_end - self.run.t0, self.rng)
        self.arrivals = sched
        arr = t_start + sched.offsets
        e, i = self.engine, 0
        while True:
            now = time.perf_counter()
            if now >= t_end:
                return
            tracer.poll(now)
            with span(self.run, "submit"):
                while i < len(arr) and arr[i] <= now:
                    self._submit(float(arr[i]))
                    sched.late.append(time.perf_counter() - arr[i])
                    i += 1
            if len(self.reqs) > self.finished:
                with span(self.run, "pump"):
                    e.pump()
            elif i < len(arr):
                with span(self.run, "wait_arrival"):
                    time.sleep(min(2e-4, max(arr[i] - time.perf_counter(),
                                             0.0)))

    def counters(self) -> dict:
        return dict(self.engine.stats, pool_high_water=self.engine.pool.high_water)

    def sample(self, check: dict, seed: int) -> list:
        """Finished requests drawn from the seed, the one with the most
        served tokens first, until ``check["sample_tokens"]`` served
        tokens or ``check["sample_requests"]`` requests."""
        min_tokens = int(check["sample_tokens"])
        max_requests = int(check["sample_requests"])
        done = [r for r in self.reqs.values() if len(r.tokens) >= r.n_out]
        if not done:
            return []
        longest = max(done, key=lambda r: (r.n_out, -r.rid))
        rest = [r for r in done if r is not longest]
        rng = np.random.default_rng(seed32(seed, 3))
        out, n = [longest], longest.n_out
        for j in rng.permutation(len(rest)):
            if n >= min_tokens or len(out) >= max_requests:
                break
            out.append(rest[j])
            n += rest[j].n_out
        return [(r.prompt, np.asarray(r.tokens[: r.n_out], np.int32))
                for r in out]

    def close(self) -> None:
        self.engine = None
        self._live = {}


def check(config: dict, seed: int, sample: list, reference,
          control: bool) -> dict:
    """Each sampled request's prompt and served tokens through the
    reference: ``token_gap`` is the widest gap by which a served token's
    reference logit lies below the reference's best."""
    params = reference.init_params(config, seed32(seed, 1))
    pad_to = int(config["engine"]["max_seq"])
    gaps, ctl = [], []
    for prompt, served in sample:
        g, c = reference.token_gaps(params, config, prompt, served, pad_to,
                                    control=control)
        gaps.append(g)
        ctl.append(c)
    out = dict(token_gap=max(gaps) if gaps else float("inf"),
               compared=int(sum(len(s) for _, s in sample)),
               requests=len(sample))
    if control:
        out["control_token_gap"] = max(ctl) if ctl else None
    return out

"""Driver: a CNN behind the request-batching ``ServeEngine``.

Stands the model up the way a user does (``repro.api.build`` ->
``compile`` -> ``serve``), with the benchmark's own weights, and drives
the ``Deployment``'s ``submit``/``pump``/``drain`` with the traffic:

* closed loop: ``clients`` callers each send one image and wait for its
  logits; every reply is followed at once by that caller's next image;
* open loop: single images at scheduled Poisson arrivals, each charged
  from its scheduled time; ``pump`` runs between arrivals so the flush
  deadline is honoured.

A cell on more than one chip serves through the program's data mesh
(``repro.launch.mesh.make_serve_mesh``), with buckets of ``max_batch``
rows a chip.

Host spans: ``collate`` wraps the runner's public ``collate`` (the host
stacking the device waits on), with the padded batch of each bucket; in
a traced run the engine's own ``serve.*`` spans join them.
"""
from __future__ import annotations

import time

import numpy as np

from .common import Run, record_engine_spans, seed32, span, timed
from .traffic import images, open_schedule


def _mix(x: int) -> int:
    """A 32-bit integer hash whose low bits depend on every input bit."""
    x &= 0xFFFFFFFF
    x = ((x >> 16) ^ x) * 0x45D9F3B & 0xFFFFFFFF
    x = ((x >> 16) ^ x) * 0x45D9F3B & 0xFFFFFFFF
    return (x >> 16) ^ x


class System:
    def __init__(self, config: dict, traffic: dict, seed: int, run: Run,
                 reference):
        import dataclasses

        from repro import api
        from repro.core.quant import PAPER_CONFIGS
        from repro.launch.mesh import make_serve_mesh
        from repro.models.cnn import ConvSpec

        self.config, self.traffic, self.run = config, traffic, run
        eng = config["engine"]
        q = config["quant"]
        quant = dataclasses.replace(PAPER_CONFIGS[q["name"]],
                                    w_bits=q["w_bits"], a_bits=q["a_bits"],
                                    first_last_fp=q["first_last_fp"])
        hw = int(config["image_hw"])
        params = reference.init_params(config, seed32(seed, 1))
        spec = [ConvSpec(**l) for l in config["layers"]]
        compiled = api.build(spec, quant, params=params, img_hw=hw,
                             name=config["name"]).compile(
            target=eng["target"], batch_hints=tuple(eng["batch_hints"]))
        del params
        self.engines = [dict(lp.engines) for lp in compiled.plan.layers]
        # a cell on n chips serves over an n-device data mesh (none for
        # one): parameters replicated, each bucket of n * max_batch rows
        # put sharded, so every chip runs the one-chip bucket
        chips = int(run.cell["chips"])
        self.dep = compiled.serve(
            max_batch=int(eng["max_batch"]) * chips,
            flush_deadline_s=float(eng["flush_deadline_ms"]) / 1e3,
            mesh=make_serve_mesh(chips))
        runner = self.dep.engine.runner
        runner.collate = timed(run, "collate", runner.collate,
                               info=lambda payloads, pad_to: dict(batch=pad_to))
        rng = np.random.default_rng(seed32(seed, 2))
        self.images = images(int(traffic["distinct_inputs"]), hw, rng)
        self.order = rng.permutation(len(self.images))
        self.rng = rng
        # the answers kept for the check: one request in ``keep_every``,
        # by a seeded hash of its rid (so every batch position is drawn),
        # so that a long window holds no more than a few hundred rows
        self.keep_every = int(config["check"]["keep_every"])
        self.keep_salt = seed32(seed, 4)
        self.answers: dict[int, tuple] = {}   # rid -> (image index, logits)
        self.nonfinite = 0                    # answers not kept that failed
        self.n_next = 0

    # -- traffic --------------------------------------------------------------

    def _next_image(self) -> int:
        i = int(self.order[self.n_next % len(self.order)])
        self.n_next += 1
        return i

    def warm(self) -> None:
        """Compile and run once every padded batch this mix dispatches."""
        for b in self.traffic["warm_batches"]:
            self.dep.predict([self.images[i] for i in range(b)])

    def _collect(self, results, index: dict) -> None:
        for r in results:
            self.run.requests.append(dict(
                t_arrive=r.t_submit, t_done=r.t_done,
                queue_wait_s=r.queue_wait_s, service_s=r.service_s))
            if _mix(r.rid ^ self.keep_salt) % self.keep_every == 0:
                self.answers[r.rid] = (index[r.rid], r.value)
            elif not np.isfinite(r.value).all():
                self.nonfinite += 1

    def drive(self, t_start: float, t_end: float, tracer) -> None:
        """Traffic from ``t_start``; the window is ``[run.t0, t_end]``."""
        merge = record_engine_spans(self.run, self.dep, tracer)
        if self.traffic["loop"] == "closed":
            self._closed(t_end, tracer)
        else:
            self._open(t_start, t_end, tracer)
        merge()

    def _closed(self, t_end: float, tracer) -> None:
        clients = int(self.traffic["clients"])
        while True:
            now = time.perf_counter()
            if now >= t_end:
                return
            tracer.poll(now)
            index = {}
            with span(self.run, "submit"):
                for _ in range(clients):
                    i = self._next_image()
                    index[self.dep.submit(self.images[i])] = i
            with span(self.run, "pump"):
                self.dep.pump()
            with span(self.run, "drain"):
                results = self.dep.drain()
            self._collect(results, index)

    def _open(self, t_start: float, t_end: float, tracer) -> None:
        sched = open_schedule(self.traffic, t_end - self.run.t0, self.rng)
        self.arrivals = sched
        arr = t_start + sched.offsets
        index, i = {}, 0
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            tracer.poll(now)
            with span(self.run, "submit"):
                while i < len(arr) and arr[i] <= now:
                    img = self._next_image()
                    index[self.dep.submit(self.images[img],
                                          t_submit=arr[i])] = img
                    sched.late.append(time.perf_counter() - arr[i])
                    i += 1
            with span(self.run, "pump"):
                self.dep.pump()
            if i < len(arr) and arr[i] > time.perf_counter():
                with span(self.run, "wait_arrival"):
                    time.sleep(min(2e-4, max(arr[i] - time.perf_counter(),
                                             0.0)))
        self._collect(self.dep.drain(), index)

    # -- after the window -----------------------------------------------------

    def counters(self) -> dict:
        return dict(self.dep.stats, engines=self.engines)

    def sample(self, check: dict, seed: int) -> list:
        """A seeded sample of ``check["sample"]`` kept answers:
        (image, logits)."""
        rids = sorted(self.answers)
        rng = np.random.default_rng(seed32(seed, 3))
        pick = rng.choice(len(rids), size=min(int(check["sample"]), len(rids)),
                          replace=False)
        return [(self.images[i], v)
                for i, v in (self.answers[rids[j]] for j in sorted(pick))]

    def close(self) -> None:
        self.dep = None
        self.answers = {}


def check(config: dict, seed: int, sample: list, reference,
          control: bool) -> dict:
    """Every sampled answer against the reference on the same image:
    ``logit_err`` is the widest max |served - reference| over a sample,
    over the reference's RMS logit.  ``distinct_answers`` and
    ``distinct_logits`` count the different answers the program served
    and the reference gives for the sample's ``distinct_images``: 1 where
    the logits do not depend on the image."""
    params = reference.init_params(config, seed32(seed, 1))
    x = np.stack([img for img, _ in sample])
    served = np.stack([v for _, v in sample]).astype(np.float64)
    ref = reference.forward(params, x, config).astype(np.float64)
    rms = np.sqrt(np.mean(ref ** 2, axis=1))
    bad = ~np.isfinite(served).all(axis=1)
    err = np.max(np.abs(served - ref), axis=1) / rms
    out = dict(logit_err=float(np.max(np.where(bad, np.inf, err))),
               compared=len(sample), nonfinite=int(bad.sum()),
               distinct_images=len({img.tobytes() for img, _ in sample}),
               distinct_answers=len({row.tobytes() for row in served}),
               distinct_logits=len({row.tobytes() for row in ref}))
    if control:
        ctl = reference.forward(params, x, config, control=True)
        out["control_logit_err"] = float(np.max(
            np.max(np.abs(ctl - ref), axis=1) / rms))
    return out

"""The serve driver: one replica on a leased chip behind ``serve.run``.

The benchmark process stays off the chip. It deploys :func:`chip_server`'s
class (the server class of the configuration's architecture, the program's
``LLMServer`` for the dense decoder, under :class:`ChipServer`'s two bridges:
a configuration taken from the cell's file, and the calls only the chip
holder can serve: trace, memory, the output check), warms the cell's shapes
through ``handle.remote``, then offers the mix's load from this one thread
for the window and drains it. What is particular to a model's layers is its
architecture's to answer (``chipbench/architectures/``).

Requests go the normal way: ``handle.remote({"tokens", "max_new_tokens"})``
-> router -> replica -> ``LLMServer.__call__`` -> ``ContinuousBatcher``.
"""

from __future__ import annotations

import gc
import shutil
import tempfile
import time
from typing import Any, Dict, List, Optional

import numpy as np

from chipbench import architectures, flops, traffic

DRAIN_S = 60.0      # how long past the window's close an answer may come
CHECK_WIDTH = 512   # the reference runs a sample at a multiple of this length
DEPLOYMENT = "chipbench-llm"


def device_fields(jax, expect: str, chips: int = 1) -> Dict[str, Any]:
    """What the leased process computes on; anything but ``chips`` devices
    of platform ``expect`` is an error (no fallback)."""
    first = jax.devices()[0]
    dev = {"platform": first.platform, "kind": first.device_kind,
           "count": len(jax.devices())}
    if dev["platform"] != expect:
        raise RuntimeError(
            f"the leased process computes on platform {dev['platform']!r} "
            f"({dev['kind']}), not {expect!r}")
    if expect == "tpu" and dev["count"] != chips:
        raise RuntimeError(
            f"the leased process sees {dev['count']} chips, the cell asks "
            f"for {chips}")
    return dev


def memory_peak(jax) -> int:
    stats = [d.memory_stats() or {} for d in jax.devices()]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


class ChipServer:
    """The benchmark's bridges, laid over the server class of the
    configuration's architecture (:func:`chip_server`): a configuration
    taken from the cell's file, and the calls only the chip holder can
    serve. Runs in the replica process, the only one that holds the chip."""

    def __init__(self, config: Dict[str, Any], engine: Dict[str, Any],
                 seed: int, expect_platform: str,
                 fault: Optional[str] = None, chips: int = 1):
        t0 = time.time()
        import jax

        self._device = device_fields(jax, expect_platform, chips)
        t_device = time.time()
        self._config, self._seed, self._fault = config, seed, fault
        self._arch = architectures.of(config)
        super().__init__(seed=seed, **self._arch.server_kwargs(config),
                         **engine)
        jax.block_until_ready(self.params)
        self._times = {"init_start": t0, "device_ready": t_device,
                       "weights_ready": time.time()}
        self._trace_dir = None

    def generate(self, tokens, max_new_tokens=None):
        out = super().generate(tokens, max_new_tokens=max_new_tokens)
        if self._fault == "token_altered" and len(out) > 1:
            # a test's planted fault: one answer token altered where it is
            # produced
            out = list(out)
            out[len(out) // 2] = (out[len(out) // 2] + 1) \
                % self._config["vocab_size"]
        return out

    def snapshot(self) -> Dict[str, Any]:
        """Counters and this process's clock, for before/after readings."""
        import jax

        out = self.stats()
        out["time"] = time.time()
        out["times"] = dict(self._times)
        out["memory_peak_bytes"] = memory_peak(jax)
        return out

    def trace_start(self) -> Dict[str, float]:
        """Start the profiler; returns when the call reached this process
        and when the profiler ran, on the host's clock."""
        import jax

        t_call = time.time()
        self._trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        jax.profiler.start_trace(self._trace_dir)
        self._trace_t0 = time.time()
        return {"called": t_call, "started": self._trace_t0}

    def trace_stop(self) -> Dict[str, Any]:
        """Stop the profiler and reduce the trace here, where it lies."""
        import jax

        from chipbench.trace import xplane

        # the traced window ends here: collecting the trace takes seconds
        t_stop = time.time()
        jax.profiler.stop_trace()
        t_read = time.time()
        try:
            reduced = xplane.reduce_dir(self._trace_dir)
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)
        reduced.update(window_s=t_stop - self._trace_t0,
                       collect_s=t_read - t_stop,
                       reduce_s=time.time() - t_read)
        return reduced

    def free(self) -> int:
        """Read the peak, then drop the weights (the engine is idle and its
        KV pool empty once every request has retired), so that the
        reference has the chip. Returns the peak."""
        import jax

        peak = memory_peak(jax)
        self.params = None
        self._engine.params = None
        gc.collect()
        return peak

    def check(self, samples: List[Dict[str, Any]],
              control: Optional[str] = None,
              detail: bool = False) -> Dict[str, Any]:
        if self.params is not None:
            raise RuntimeError("free() the weights before the check")
        return check_samples(self._config, self._seed, samples, control,
                             detail)

    def reseed(self, seed: int) -> bool:
        """New weights from ``seed`` under the compiled programs (the
        limits tool reads a dozen seeds in one process)."""
        import jax

        self._seed = seed
        self.free()
        self.params = self._arch.init_program_params(
            jax.random.PRNGKey(seed), self.cfg)
        self._engine.params = self.params
        return True


def chip_server(cfg: Dict[str, Any]) -> type:
    """The class that is deployed: the bridges over the server class of
    the configuration's architecture."""
    return type("ChipLLMServer",
                (ChipServer, architectures.of(cfg).server_class()), {})


def check_samples(cfg: Dict[str, Any], seed: int,
                  samples: List[Dict[str, Any]],
                  control: Optional[str] = None,
                  detail: bool = False) -> Dict[str, Any]:
    """The widest gap by which a served token's reference logit lies below
    the reference's best, over ``samples`` (prompt and served tokens). The
    reference is the architecture's and makes its own weights from the
    seed; each sample runs at its own length, rounded up to
    ``CHECK_WIDTH``. With ``control`` the same reading is also taken, at the
    same served positions, of the token a lower precision puts first there.
    ``detail`` adds one row a sample (the limits tool reads a sample size
    from them): prompt length, served tokens, the two gaps, the control's
    gap at the first served token, and at how many served positions the
    control put another token first."""
    import jax
    import jax.numpy as jnp

    model = architectures.of(cfg).reference()
    t0 = time.time()
    params = model.init_params(jax.random.PRNGKey(seed), cfg)
    out_width = -(-max(len(s["served"]) for s in samples) // 128) * 128

    def gaps(p, tokens, served_at, start, n_served, compute):
        ref = model.logits(p, tokens, cfg)
        best = jnp.max(ref, axis=-1)
        live = jnp.arange(out_width) < n_served
        pos = jnp.where(live, start + jnp.arange(out_width), 0)
        out = {"served": jnp.where(live, best[pos] - ref[pos, served_at], 0.)}
        if compute is not None:
            low = jnp.argmax(model.logits(p, tokens, cfg, compute), -1)
            out["control"] = jnp.where(live, best[pos] - ref[pos, low[pos]],
                                       0.)
        return out

    fn = jax.jit(gaps, static_argnames=("compute",))
    worst = {"served": 0.0, "control": 0.0}
    tokens_compared, rows = 0, []
    for s in samples:
        n_p, served = len(s["prompt"]), list(s["served"])
        seq = (list(s["prompt"]) + served)[:-1]
        toks = np.ones((-(-len(seq) // CHECK_WIDTH) * CHECK_WIDTH,), np.int32)
        toks[:len(seq)] = seq
        served_at = np.zeros((out_width,), np.int32)
        served_at[:len(served)] = served
        out = {k: np.asarray(v) for k, v in fn(
            params, jnp.asarray(toks), jnp.asarray(served_at),
            jnp.int32(n_p - 1), jnp.int32(len(served)), control).items()}
        for k, v in out.items():
            worst[k] = max(worst[k], float(v.max()))
        tokens_compared += len(served)
        if detail:
            c = out.get("control", np.zeros(1))
            rows.append([n_p, len(served), float(out["served"].max()),
                         float(c.max()), float(c[0]), int((c > 0).sum())])
    del params
    gc.collect()
    out = {"gap_max": worst["served"], "tokens": tokens_compared,
           "requests": len(samples), "seconds": time.time() - t0}
    if control is not None:
        out["control_gap_max"] = worst["control"]
    if detail:
        out["per_sample"] = rows
    return out


# ---------------------------------------------------------------- the load
def engine_kwargs(cfg: Dict[str, Any], mix: Dict[str, Any]) -> Dict[str, Any]:
    """``LLMServer``'s engine settings of a mix; the KV pool holds what
    every slot can need at once (longest prompt plus budget, page
    aligned), not ``max_seq`` per slot."""
    e = dict(mix["engine"])
    e.pop("max_concurrent_queries", None)
    page = e["kv_page_tokens"]
    need = mix["prompt_tokens"]["max"] + e["max_new_tokens"]
    cap = min(-(-need // page) * page, cfg["max_position_embeddings"])
    if "kv_pool_bytes" not in e:
        e["kv_pool_bytes"] = e["max_batch_size"] * cap \
            * architectures.of(cfg).cache_token_bytes(cfg)
    return e


def warm_up_waves(mix: Dict[str, Any]) -> List[List[Dict[str, int]]]:
    """(prompt length, budget) pairs, in waves sent together, that make the
    engine build every program the mix can reach. With the paged pool those
    are one prefill a bucket and the one decode step, which the first
    request builds. The pairs cover more than that, each (prefill bucket,
    reserved capacity in whole pages) pair, so that a program keyed on a
    row's capacity would be built here too and not inside a window. In the
    wave of capacity S the requests that reserve S sit beside one request of
    each shorter capacity; every budget lasts three iterations where the
    clips allow it."""
    e = mix["engine"]
    pad, page, k = e["pad_multiple"], e["kv_page_tokens"], e["steps_per_iter"]
    lo_p, hi_p = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    lo_b = mix["output_tokens"]["min"]
    hi_b = min(mix["output_tokens"]["max"], e["max_new_tokens"])
    stay = min(max(lo_b, 3 * k + 1), hi_b)
    up = lambda x, m: -(-x // m) * m  # noqa: E731

    def cap_of(p, b):
        return up(max(up(p, pad), p + b), page)

    pairs: Dict[Any, Dict[str, int]] = {}
    for bucket in range(up(lo_p, pad), up(hi_p, pad) + 1, pad):
        p_hi, p_lo = min(bucket, hi_p), max(bucket - pad + 1, lo_p)
        for p in (max(p_lo, p_hi - stay), p_hi, p_lo):
            for b in range(lo_b, hi_b + 1):
                key = (bucket, cap_of(p, b))
                best = pairs.get(key)
                # the shortest budget that stays, else the longest there is
                if best is None or (best["budget"] < stay and b > best[
                        "budget"]):
                    pairs[key] = {"prompt": p, "budget": b}
    caps = sorted({c for _, c in pairs})
    cheapest = {c: min((v for (_, cc), v in pairs.items() if cc == c),
                       key=lambda v: v["budget"]) for c in caps}
    # a pair that only a long budget reaches rides in the first wave when a
    # cheaper pair can hold its own slab length
    long = {key for key, v in pairs.items()
            if v["budget"] > stay + 1 and cheapest[key[1]] is not v}
    waves = [[v for key, v in pairs.items() if key[1] == s
              and key not in long] + [cheapest[c] for c in caps if c < s]
             for s in reversed(caps)]
    waves[0] += [pairs[key] for key in sorted(long)]
    return waves


class Load:
    """The window's bookkeeping: what was sent, when it was due, when its
    answer came."""

    def __init__(self, api, handle, clock):
        self.api, self.handle, self.clock = api, handle, clock
        self.pending: Dict[Any, Dict[str, Any]] = {}
        self.done: List[Dict[str, Any]] = []

    def send(self, req: Dict[str, Any], due: float, **tags) -> None:
        rec = dict(tags, due=due, prompt=req["tokens"],
                   budget=req["max_new_tokens"])
        rec["sent"] = self.clock()
        try:
            ref = self.handle.remote({"tokens": req["tokens"],
                                      "max_new_tokens":
                                          req["max_new_tokens"]})
        except Exception as e:  # noqa: BLE001 — a refusal is a failure
            rec.update(finished=self.clock(), error=repr(e))
            self.done.append(rec)
            return
        self.pending[ref] = rec

    def collect(self, timeout: float) -> List[Dict[str, Any]]:
        """Wait up to ``timeout`` for one answer; stamp and read every
        answer that is there."""
        if not self.pending:
            time.sleep(max(0.0, timeout))
            return []
        refs = list(self.pending)
        ready, _ = self.api.wait(refs, num_returns=1, timeout=timeout)
        if ready:
            ready, _ = self.api.wait(refs, num_returns=len(refs), timeout=0)
        now = self.clock()
        out = []
        for ref in ready:
            rec = self.pending.pop(ref)
            rec["finished"] = now
            try:
                rec["served"] = self.api.get(ref, timeout=30)["tokens"]
            except Exception as e:  # noqa: BLE001
                rec["error"] = repr(e)
            self.done.append(rec)
            out.append(rec)
        return out

    def drain(self, deadline: float) -> None:
        while self.pending and self.clock() < deadline:
            self.collect(min(0.25, max(0.0, deadline - self.clock())))
        for rec in self.pending.values():
            rec.update(finished=self.clock(), error="no answer by the "
                       f"drain's end ({DRAIN_S} s past the close)")
            self.done.append(rec)
        self.pending.clear()


class OpenLoop:
    """Open loop: send each request when it is due, whatever came back."""

    def __init__(self, load: Load, schedule):
        self.load, self.schedule, self.i = load, schedule, 0

    def run_until(self, end: float) -> None:
        load, schedule = self.load, self.schedule
        while True:
            now = load.clock()
            while self.i < len(schedule) and schedule[self.i]["due"] <= min(
                    now, end):
                load.send(schedule[self.i], schedule[self.i]["due"])
                self.i += 1
                now = load.clock()
            if now >= end:
                return
            nxt = schedule[self.i]["due"] if self.i < len(schedule) else end
            load.collect(max(0.0, min(nxt, end) - now))


class ClosedLoop:
    """Closed loop: each client sends its next request when its last one
    returns; a request is due the instant its client is free. ``dry``
    counts the clients that had sent all the mix gives them before the
    window closed: such a run offered less than a closed loop does."""

    def __init__(self, load: Load, clients):
        self.load, self.clients = load, clients
        self.nxt = [0] * len(clients)
        self.started = False
        self.dry = 0

    def _send(self, c: int) -> None:
        if self.nxt[c] < len(self.clients[c]):
            self.load.send(self.clients[c][self.nxt[c]], self.load.clock(),
                           client=c)
            self.nxt[c] += 1
        else:
            self.dry += 1

    def run_until(self, end: float) -> None:
        load = self.load
        if not self.started:
            self.started = True
            for c in range(len(self.clients)):
                self._send(c)
        while load.clock() < end:
            for rec in load.collect(min(0.25, max(0.0, end - load.clock()))):
                if load.clock() < end:
                    self._send(rec["client"])


def percentile(values: List[float], q: float) -> float:
    """The q-th percentile by the nearest rank above (no interpolation
    across the tail)."""
    v = sorted(values)
    return v[min(len(v) - 1, int(np.ceil(q / 100.0 * len(v))) - 1)]


class Served:
    """A deployed, warmed replica of one cell, and windows of load on it.
    One run is one window; the rate sweep and the limits tool
    (``chipbench/sweep.py``, ``chipbench/limits.py``) take several."""

    def __init__(self, cell, cfg, mix, *, seed: int, expect_platform: str,
                 fault: Optional[str] = None):
        import ray_memory_management_tpu as rmt
        from ray_memory_management_tpu import serve
        from ray_memory_management_tpu.serve.deployment import deployment

        self.rmt, self.serve = rmt, serve
        self.cfg, self.mix = cfg, mix
        rmt.init(num_cpus=4, num_tpus=cell["chips"])
        try:
            serve.start(http_port=None)
            t_deploy = time.time()
            self.handle = serve.run(deployment(
                chip_server(cfg), name=DEPLOYMENT,
                ray_actor_options={"num_tpus": cell["chips"]},
                max_concurrent_queries=mix["engine"].get(
                    "max_concurrent_queries", 100),
            ).bind(cfg, engine_kwargs(cfg, mix), seed, expect_platform,
                   fault, cell["chips"]))
            self.first = self.call("snapshot", timeout=600)
            self.lease_to_device_s = \
                self.first["times"]["device_ready"] - t_deploy
            # warm-up: every shape of the cell, through the normal entry
            rng = np.random.default_rng([seed, 3])
            for wave in warm_up_waves(mix):
                refs = [self.handle.remote({
                    "tokens": rng.integers(2, cfg["vocab_size"],
                                           w["prompt"]).tolist(),
                    "max_new_tokens": w["budget"]}) for w in wave]
                for r in refs:
                    rmt.get(r, timeout=900)
        except BaseException:
            self.close()
            raise

    def call(self, method: str, *args, timeout: float = 300):
        return self.rmt.get(getattr(self.handle, method).remote(*args),
                            timeout=timeout)

    def close(self) -> None:
        try:
            self.serve.shutdown()
        finally:
            self.rmt.shutdown()

    def window(self, seed: int, seconds: float, trace: bool = False,
               mix: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        """Offer the mix's load for ``seconds`` and wait for the answers."""
        mix, vocab = mix or self.mix, self.cfg["vocab_size"]
        open_loop = mix["kind"] == "serve-open"
        work = traffic.open_schedule(mix, seed, seconds, vocab) \
            if open_loop else traffic.closed_clients(
                mix, seed, int(mix.get("requests_per_client", 64)), vocab)
        before = self.call("snapshot")
        t0 = time.time()
        load = Load(self.rmt, self.handle, lambda: time.time() - t0)
        offer = (OpenLoop if open_loop else ClosedLoop)(load, work)
        if trace:
            # the window's last seconds, traced in the chip holder; the
            # trace is collected and reduced there once the window is shut.
            # The sender does not wait for the call that starts it: every
            # request due meanwhile would be sent late
            offer.run_until(max(0.0, seconds - float(
                mix.get("trace_seconds", 5.0))))
            t_asked = time.time()
            start_ref = self.handle.trace_start.remote()
        offer.run_until(seconds)
        window_s = load.clock()
        if trace:
            began = self.rmt.get(start_ref, timeout=300)
        after = self.call("snapshot")
        # the trace is collected while the last answers come in
        stop_ref = self.handle.trace_stop.remote() if trace else None
        load.drain(seconds + DRAIN_S)
        reduced = None
        if trace:
            reduced = self.rmt.get(stop_ref, timeout=300)
            # what the trace cost: the call's way to the replica, and the
            # profiler's start there (one host, one clock)
            reduced.update(dispatch_s=began["called"] - t_asked,
                           start_s=began["started"] - began["called"])
        return {"t0": t0, "window_s": window_s, "before": before,
                "after": after, "done": load.done, "trace": reduced,
                "seconds": seconds, "dry": getattr(offer, "dry", 0)}

    def check(self, seed: int, done, control: Optional[str] = None,
              detail: bool = False):
        """Free the weights, then compare a sample of the answers, drawn
        from the seed with the longest in it, with the reference."""
        peak = self.call("free")
        ok = sorted((r for r in done if "served" in r),
                    key=lambda r: -len(r["served"]))
        pick = np.random.default_rng([seed, 4]).permutation(
            max(0, len(ok) - 1))[:max(0, int(
                self.mix["check"]["requests"]) - 1)]
        sample = ok[:1] + [ok[1:][i] for i in pick]
        compared = {"gap_max": float("inf"), "tokens": 0, "requests": 0}
        if sample:
            compared = self.call("check", [
                {"prompt": r["prompt"], "served": r["served"]}
                for r in sample], control, detail, timeout=600)
        return peak, compared


def summarize(w: Dict[str, Any], cfg, mix) -> Dict[str, Any]:
    """A window's numbers. Tokens: each answered request's prompt and
    output tokens, credited by the share of its time in service (due
    instant to completion) that lies inside the window, so a request that
    completes inside counts whole, one still in service at the close counts
    for the part done, and an answer a moment before or after the close
    reads the same. Latency: each request's time from the instant it was due
    over its output tokens (one with no answer counts the whole drain)."""
    done, seconds, close = w["done"], w["seconds"], w["window_s"]
    arch = architectures.of(cfg)
    ok = [r for r in done if "served" in r]
    lat = [(r["finished"] - r["due"]) / max(1, len(r["served"])) * 1e3
           if "served" in r else
           (seconds + DRAIN_S - r["due"]) / r["budget"] * 1e3 for r in done]
    sizes = [len(r["prompt"]) + len(r["served"]) for r in ok]
    share = [min(1.0, max(0.0, (min(r["finished"], close) - r["due"])
                          / max(r["finished"] - r["due"], 1e-9)))
             for r in ok]
    return {
        "ok": ok, "failed": len(done) - len(ok),
        "tokens": sum(n * c for n, c in zip(sizes, share)),
        "latency_ms_per_token": lat,
        "model_flops": sum(c * arch.forward_flops(
            cfg, n, flops.causal_pairs(n)) for n, c in zip(sizes, share)),
        "late_ms": [(r["sent"] - r["due"]) * 1e3 for r in done],
        "requests_in_window": sum(r["finished"] <= close for r in ok),
        "wrong_length": sum(len(r["served"]) != r["budget"] for r in ok),
        "last_finished": max((r["finished"] for r in done), default=0.0),
    }


def run(cell: Dict[str, Any], cfg: Dict[str, Any], mix: Dict[str, Any], *,
        seed: int, seconds: float, trace: bool, started: float,
        expect_platform: str = "tpu", fault: Optional[str] = None,
        control: Optional[str] = None) -> Dict[str, Any]:
    """One run of a serve cell; returns what the harness prints."""
    served = Served(cell, cfg, mix, seed=seed,
                    expect_platform=expect_platform, fault=fault)
    try:
        w = served.window(seed, seconds, trace)
        peak, compared = served.check(seed, w["done"], control)
    finally:
        served.close()
    first, done = served.first, w["done"]
    s = summarize(w, cfg, mix)
    failed, window_s, setup_s = s["failed"], w["window_s"], w["t0"] - started
    limit = float(mix["check"]["gap_limit"])
    comparisons = {
        "platform": [first["device"]["platform"], expect_platform],
        "answers_missing_or_failed": [failed, 0],
        "answers_of_wrong_length": [s["wrong_length"], 0],
        "served_logit_gap_max": [compared["gap_max"], limit],
        "tokens_compared": [compared["tokens"], 1],
    }
    correct = (failed == 0 and not s["wrong_length"] and bool(done)
               and compared["tokens"] >= 1 and compared["gap_max"] <= limit)
    if mix["kind"] == "serve-closed":
        # a client with nothing left to send: the rate read is not capacity
        comparisons["clients_out_of_work"] = [w["dry"], 0]
        correct = correct and w["dry"] == 0
    if control is not None:
        comparisons["control_logit_gap_max"] = [
            compared.get("control_gap_max"), limit]
    clocks = {k: s[k] for k in ("tokens", "model_flops", "late_ms",
                                "latency_ms_per_token",
                                "requests_in_window", "last_finished")}
    clocks.update(setup_s=setup_s, window_s=window_s,
                  lease_to_device_s=served.lease_to_device_s)
    values = {"setup_s": setup_s}
    if window_s > 0 and s["tokens"]:
        # one reading, named for what it is: below the knee of an open loop
        # the offered load less the window's edge, in a closed loop capacity
        values["serve.capacity_tokens_per_s" if mix["kind"] == "serve-closed"
               else "serve.tokens_per_s"] = s["tokens"] / window_s
    if s["latency_ms_per_token"] and mix["kind"] == "serve-open":
        values["serve.norm_latency_p90_ms"] = percentile(
            s["latency_ms_per_token"], 90)
    device = dict(first["device"], memory_peak_bytes=peak)
    return {"correct": correct, "attempted": len(done), "failed": failed,
            "values": values, "device": device, "comparisons": comparisons,
            "check_s": compared.get("seconds"),
            "context": {"kind": "serve", "cfg": cfg, "mix": mix,
                        "before": w["before"], "after": w["after"],
                        "clocks": clocks, "trace": w["trace"],
                        "device": device}}

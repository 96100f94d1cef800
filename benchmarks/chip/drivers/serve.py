"""Driver for serving cells: ``ServeEngine.tick`` as a user's server loop
calls it, built by ``build_serve_engine`` at the configuration's widths.

Set-up: the benchmark's own weights from the seed, the engine (with a
durable session pool when the mix asks for one), and a warm-up that
sends one request of every prompt length of the mix through admission,
prefill, slot decode, a commit and retirement, so that every program
the window calls is compiled before it opens.

Window: one thread.  A backlog mix keeps ``depth`` requests pending
before every tick; its window opens once every slot is running (and,
with a pool, right after a committing tick) and closes after the first
committing tick past ``--seconds``, so it holds whole commit cycles.  An
open-loop mix submits each request once its due time has passed, and
times it from then.  Tokens are stamped at the end of the tick that
emitted them.

After the window: ``memory_peak_bytes`` is read, the engine is freed, and
the plain float32 reference runs over a sample of finished requests
(prompt plus served tokens); a durable mix also reads the newest
committed session table back from the pool.
"""
from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import chiplib                                            # noqa: E402
import flops                                              # noqa: E402
from traffic.generator import check_mix, serve_stream     # noqa: E402

#: after the window, requests due inside it get this long to answer
LATE_GRACE_S = 60.0
#: finished requests the reference checks after the window
CHECK_REQUESTS = 6


class Server:
    """The engine under test and what the window records of it."""

    def __init__(self, a: chiplib.RunArgs, *, params=None):
        import jax
        from repro.dsm.api import CXL0Config
        from repro.launch.mesh import make_debug_mesh
        from repro.models.registry import build
        from repro.parallel.sharding import ctx_for_mesh
        from repro.serve.engine import build_serve_engine

        self.a, self.mix, self.cfg_file = a, a.traffic, a.config
        self.cfg = chiplib.program_config(a.config)
        mix = self.mix
        check_mix(mix, mix["t_max"])
        self.bundle = build(self.cfg, dec_pos_len=mix["t_max"])
        if params is None:
            params = chiplib.arch(a.config).make_params(
                self.bundle.abstract_params(), a.seed, a.config)
        durable = mix.get("durable")
        self.pool = None
        dsm = None
        if durable:
            self.pool = tempfile.mkdtemp(prefix="chipbench_sessions_")
            dsm = CXL0Config(path=self.pool, schedule=durable["schedule"],
                             retention=durable["retention"])
        self.engine, _ = build_serve_engine(
            a.config["arch"], smoke=False, n_slots=mix["n_slots"],
            t_max=mix["t_max"],
            ctx=ctx_for_mesh(make_debug_mesh(jax.device_count())),
            dsm=dsm,
            commit_every=durable["commit_every"] if durable else 0,
            retire_done=bool(durable and durable.get("retire_done")),
            bundle=self.bundle, params=params)
        del params
        if durable:
            self.engine.resume()             # a cold pool: nothing to resume
        self.rid_prefix = "r"                # request ids: prefix + index
        self.requests = {}                   # rid -> generator Arrival
        self.due = {}                        # rid -> perf_counter due time
        self.tokens = {}                     # rid -> [perf_counter stamps]
        self.live = {}                       # rid -> tokens seen so far
        self.ticks = []                      # one dict per window tick
        self.lateness = []                   # submit - due, open loop

    # -- the engine's calls ----------------------------------------------
    def submit(self, arr, due: float, now: float):
        from repro.serve.scheduler import Request
        self.requests[arr.rid] = arr
        self.due[arr.rid] = due
        self.tokens[arr.rid] = []
        self.live[arr.rid] = 0
        self.engine.submit([Request(arr.rid, arr.prompt,
                                    arr.max_new_tokens)])
        self.lateness.append(now - due)

    def tick(self, spans):
        eng = self.engine
        commits0 = eng._n_commits
        t_a = time.perf_counter()
        with spans("serve.tick"):
            eng.tick()
        t_b = time.perf_counter()
        prefill, decode, keys = 0.0, 0.0, 0
        n_tokens = slots = decode_slots = 0
        for rid, seen in list(self.live.items()):
            s = eng.sessions.get(rid)
            done = rid in eng.results
            n = len(eng.results[rid]) if done else (len(s.emitted) if s
                                                     else 0)
            plen = len(self.requests[rid].prompt)
            for k in range(seen + 1, n + 1):       # k-th output token
                if k == 1:
                    prefill += flops.prefill_flops(self.cfg_file, plen)
                else:
                    pos = plen + k - 2
                    decode += flops.decode_flops(self.cfg_file, pos)
                    keys += pos + 1
            self.tokens[rid].extend([t_b] * (n - seen))
            n_tokens += n - seen
            slots += n > seen
            decode_slots += n > max(seen, 1)       # a token past the first
            if done:
                del self.live[rid]
            else:
                self.live[rid] = n
        self.ticks.append({"t0": t_a, "t1": t_b,
                           "commit": eng._n_commits != commits0,
                           "tokens": n_tokens, "slots": slots,
                           "decode_slots": decode_slots,
                           "prefill_flops": prefill,
                           "decode_flops": decode, "decode_keys": keys})

    def warm(self):
        """Every prompt length once, decoding past a commit."""
        from repro.serve.scheduler import Request
        mix, eng = self.mix, self.engine
        rng = np.random.default_rng(0)
        budget = 2 + (mix["durable"]["commit_every"] if mix.get("durable")
                      else 0)
        eng.submit([Request(f"warm{i}", tuple(int(t) for t in rng.integers(
            0, self.cfg.vocab_size, size=L)), budget)
            for i, L in enumerate(mix["prompt_lens"])])
        while not eng.sched.done:
            eng.tick()

    def close(self):
        self.engine.close()
        if self.pool:
            shutil.rmtree(self.pool, ignore_errors=True)


def drive(srv: Server, window: chiplib.Window, spans, counter,
          profile=None) -> int:
    """The measured window, then the grace for requests due inside it.
    Returns the compilations counted inside the window."""
    compiles0 = counter.n
    mix, eng = srv.mix, srv.engine
    stream = serve_stream(mix, srv.a.seed, srv.cfg.vocab_size,
                          prefix=srv.rid_prefix)
    arrival = mix["arrival"]
    backlog = arrival["kind"] == "backlog"
    nxt = None if backlog else next(stream)
    durable = bool(mix.get("durable"))

    def top_up(now):
        while len(eng.sched.pending) < arrival["depth"]:
            with spans("serve.submit"):
                srv.submit(next(stream), now, now)

    if backlog:
        # open on a full engine right after a commit, and close right
        # after one: the window holds whole commit cycles
        while not (srv.ticks and (srv.ticks[-1]["commit"] or not durable)
                   and eng.sched.n_running == eng.n_slots):
            top_up(time.perf_counter())
            srv.tick(spans)
        srv.ticks.clear()
    with spans("bench.window"):
        window.open()
        while True:
            now = time.perf_counter()
            if window.due() and (not durable or (srv.ticks and
                                                 srv.ticks[-1]["commit"])):
                window.close()
                compiles = counter.n - compiles0
                break
            if backlog:
                top_up(now)
            else:
                while window.t0 + nxt.due_s <= now:
                    with spans("serve.submit"):
                        srv.submit(nxt, window.t0 + nxt.due_s, now)
                    nxt = next(stream)
                if eng.sched.done:
                    wait = min(window.t0 + nxt.due_s, window.deadline) - now
                    if wait > 0:
                        time.sleep(wait)
                    continue
            srv.tick(spans)
    if profile is not None:
        profile.__exit__(None, None, None)
    if not backlog:                      # answers due in the window
        stop = window.t1 + LATE_GRACE_S
        while (any(not srv.tokens[r] for r in srv.tokens)
               and time.perf_counter() < stop and not eng.sched.done):
            srv.tick(spans)
    srv.t_end = time.perf_counter()
    return compiles


def end_to_end(srv: Server, window: chiplib.Window) -> dict:
    """Rates over the whole window; the time to first token of every
    request due in it, one still unanswered counting its wait so far."""
    t0, t1 = window.t0, window.t1
    rids = list(srv.tokens)
    out = {}
    in_window = sum(1 for r in rids for t in srv.tokens[r] if t0 <= t <= t1)
    out["serve_tokens_per_s"] = in_window / window.length
    ttft = [(srv.tokens[r][0] if srv.tokens[r] else srv.t_end)
            - srv.due[r] for r in rids if srv.due[r] <= t1]
    gaps = [b - a for r in rids
            for a, b in zip(srv.tokens[r], srv.tokens[r][1:]) if b <= t1]
    if ttft:
        out["ttft_p95_ms"] = 1e3 * chiplib.percentile(ttft, 95)
    if gaps:
        out["itl_p95_ms"] = 1e3 * chiplib.percentile(gaps, 95)
    return out


def occupancy(srv: Server, window: chiplib.Window) -> str:
    """How full the engine ran in the window: slots that emitted a token
    per tick, and the K/V bytes of the positions its decoding slots read,
    against the lanes the engine reserves."""
    ticks = [t for t in srv.ticks if window.t0 <= t["t0"]
             and t["t1"] <= window.t1]
    if not ticks:
        return "occupancy: no ticks"
    per_tok = flops.kv_bytes_per_token(srv.cfg_file)
    slots = np.asarray([t["slots"] for t in ticks], np.float64)
    live = np.asarray([t["decode_keys"] for t in ticks], np.float64) * per_tok
    reserved = per_tok * srv.mix["t_max"] * srv.mix["n_slots"]
    return (f"occupancy: live slots per tick mean {slots.mean():.2f} peak "
            f"{int(slots.max())} of {srv.mix['n_slots']}; live K/V bytes "
            f"mean {live.mean():.0f} peak {live.max():.0f} of {reserved} "
            f"reserved")


def sample_finished(srv: Server, seed: int, k: int):
    """k finished requests drawn from the seed, the longest answer among
    them."""
    done = sorted(r for r in srv.engine.results if r in srv.requests)
    if not done:
        return []
    longest = max(done, key=lambda r: (len(srv.engine.results[r]), r))
    rest = [r for r in done if r != longest]
    rng = np.random.default_rng([int(seed), 13])
    pick = list(rng.choice(rest, size=min(k - 1, len(rest)),
                           replace=False)) if rest else []
    return [longest] + sorted(pick)


def pool_mismatches(srv: Server) -> tuple:
    """The newest committed session table, read back through a fresh
    context over the pool: how many sessions' committed tokens are not
    what the engine served, out of how many sessions."""
    from repro.dsm.api import CXL0Config
    from repro.serve.sessions import SessionStore
    eng = srv.engine
    ctx = CXL0Config(path=srv.pool).open()
    try:
        rec = SessionStore(ctx=ctx).recover(eng.kv.template1,
                                            pager=eng.pager)
    finally:
        ctx.close()
    if rec is None:
        return 1, 0
    bad = 0
    for rid, s in rec.sessions.items():
        served = (eng.results.get(rid)
                  or (eng.sessions[rid].emitted if rid in eng.sessions
                      else None))
        if served is None or list(served[:len(s.emitted)]) != list(
                s.emitted) or not s.emitted:
            bad += 1
    return bad, len(rec.sessions)


def reference_gaps(a: chiplib.RunArgs, picks, quant=None):
    """Widest gap of the served tokens (and of the quantized reference's
    own picks, with ``quant``) over the sampled requests."""
    from repro.models.registry import build
    ref = chiplib.reference(a.config)
    cfg = chiplib.program_config(a.config)
    abstract = build(cfg, dec_pos_len=a.traffic["t_max"]).abstract_params()
    params = ref.to_f32(chiplib.arch(a.config).make_params(
        abstract, a.seed, a.config))
    worst, worst_ctrl, n = 0.0, None, 0
    for prompt, served in picks:
        g, c = ref.served_gaps(a.config, params, prompt, served, quant)
        worst = max(worst, float(g.max()))
        n += len(served)
        if c is not None:
            worst_ctrl = max(worst_ctrl or 0.0, float(c.max()))
    del params
    return worst, worst_ctrl, n


def run(a: chiplib.RunArgs) -> chiplib.RunRecord:
    import jax
    log = a.log
    srv = Server(a)
    srv.warm()
    spans = chiplib.Spans(a.trace)
    if a.trace:
        for name in ("_admit", "_decode_tick", "_commit"):
            setattr(srv.engine, name,
                    spans.wrap("serve." + name.strip("_"),
                               getattr(srv.engine, name)))
    window = chiplib.Window(a.seconds)
    profile = chiplib.Profile(tempfile.mkdtemp(
        prefix="chipbench_trace_")) if a.trace else None
    if profile is not None:
        profile.__enter__()
    compiles = drive(srv, window, spans, a.counter, profile)
    e2e = end_to_end(srv, window)
    e2e["setup_s"] = window.t0 - a.t_start
    mem = chiplib.memory_peak_bytes(jax.devices())
    late = np.asarray(srv.lateness) * 1e3
    if len(late):
        log(f"generator lateness ms: p50 {np.percentile(late, 50):.3f} "
            f"p95 {np.percentile(late, 95):.3f} max {late.max():.3f} "
            f"over {len(late)} requests")
    if srv.mix["arrival"]["kind"] == "backlog":
        # a backlog request is attempted once admitted; the rest of the
        # queue at the close was never due
        due_in = [r for r in srv.tokens
                  if srv.tokens[r] and srv.tokens[r][0] <= window.t1]
    else:
        due_in = [r for r in srv.tokens if srv.due[r] <= window.t1]
    answered = [r for r in due_in if srv.tokens[r]]
    log(f"window {window.length:.3f} s: {len(srv.ticks)} ticks, "
        f"{sum(t['commit'] for t in srv.ticks)} committing, "
        f"{len(due_in)} requests due, {len(answered)} answered, "
        f"{len(srv.engine.results)} finished in all")

    log(occupancy(srv, window))
    picks = [(srv.requests[r].prompt, list(srv.engine.results[r]))
             for r in sample_finished(srv, a.seed, CHECK_REQUESTS)]
    checks = []
    if srv.pool:
        bad, n_sess = pool_mismatches(srv)
        log(f"pool read-back: {n_sess} committed sessions, {bad} not as "
            f"served")
        checks.append(chiplib.Check("pool_sessions_not_as_served",
                                    float(bad if n_sess else 1), 0.0))
    host = {"ticks": srv.ticks, "window": (window.t0, window.t1)}
    srv.close()
    del srv
    gc.collect()
    gap, _, n_tok = reference_gaps(a, picks)
    log(f"reference: {len(picks)} finished requests, {n_tok} served tokens")
    checks.insert(0, chiplib.Check("served_logit_gap", gap,
                                   a.limits.get("served_logit_gap",
                                                float("nan"))))
    return chiplib.RunRecord(
        end_to_end=e2e, checks=checks,
        attempted=len(due_in), failed=len(due_in) - len(answered),
        memory_peak_bytes=mem, host=host, window_s=window.length,
        trace_path=profile.xplane() if profile else None,
        compiles_in_window=compiles)

"""Online serving: ``DFRServer.step`` ticks under a closed loop of clients.

Set-up makes the stream pool and the mask from the seed, builds the
server on the configuration's slab and warms both step variants (fold-only
and fold+solve).  Each client then sends its first stream (staggered
lengths, so completions spread over the ticks); whenever a stream
completes, a client sends the next one from the pool, which the server
admits at its next tick.  The window runs ticks until ``--seconds`` have
passed.  Each tick is timed by the benchmark's clock around the whole
``step()``: packing, the device step, retiring and the guard sync.

After the window a sample of the completed streams, drawn from the seed
with a full-length one in it, is replayed by the reference from each
stream's admission tick, and every served prediction is compared.
"""

from __future__ import annotations

import json
import time

import numpy as np

from bench import reference
from bench.traffic import generate

KERNELS = ("dfr_scan", "ridge_gram_into")


def session_config(config: dict):
    from repro.core import nonlinear
    from repro.pipeline.session import SessionConfig

    serve = config["serve"]
    params = dict(config["model"])
    model = getattr(nonlinear, params.pop("name"))(**params)
    return SessionConfig(
        model=model, n_nodes=int(config["n_nodes"]), n_channels=1,
        washout=int(config["washout"]),
        ridge_l2=tuple(float(v) for v in config["ridge_l2"]),
        chunk_k=int(serve["chunk_k"]), forgetting=float(serve["forgetting"]),
        refresh_every=int(serve["refresh_every"]),
        state_method=serve["state_method"], use_kernel=bool(serve["use_kernel"]),
        state_dtype=serve["state_dtype"])


def _faulty_step(fault: str):
    """A session step with one of the planted faults."""
    import jax
    import jax.numpy as jnp

    from repro.pipeline.session import _session_step

    real = jax.jit(_session_step, static_argnames=("cfg", "refresh"))

    def step(cfg, mask, state, jc, yc, **kw):
        y_hat, new = real(cfg, mask, state, jc, yc, **kw)
        if fault == "unchanged_state":
            return jnp.zeros_like(y_hat), state
        if fault == "half_batch":
            # only the first half of the slots is stepped
            half = jnp.arange(jc.shape[0]) < jc.shape[0] // 2
            new = jax.tree.map(
                lambda n, o: jnp.where(half.reshape((-1,) + (1,) * (n.ndim - 1)), n, o),
                new, state)
            return jnp.where(half[:, None, None], y_hat, 0.0), new
        if fault == "alter_answer":
            return y_hat.at[:, 0, :].add(1.0), new
        raise ValueError(f"unknown fault {fault!r}")

    return step


class Clients:
    """Closed loop: each completion sends one more stream from the pool."""

    def __init__(self, server, pool, mix):
        from repro.launch.serve_dfr import StreamRequest

        self.make = StreamRequest
        self.server, self.pool, self.chunk = server, pool, server.cfg.chunk_k
        self.sent = []          # [request, pool index, admission tick]
        self.admitted = 0       # requests of ``sent`` the server has taken
        self.done = 0           # completions seen
        for client in range(int(mix["clients"])):
            self.send(generate.first_wave_chunks(mix, client))

    def send(self, chunks: int) -> None:
        k = len(self.sent) % len(self.pool)
        j, y = self.pool[k]
        req = self.make(rid=len(self.sent), j=j[:chunks * self.chunk],
                        y=y[:chunks * self.chunk])
        self.sent.append([req, k, None])
        self.server.submit(req)

    def after_tick(self, queued_before: int, full_chunks: int) -> None:
        took = queued_before - len(self.server.queue)
        for entry in self.sent[self.admitted:self.admitted + took]:
            entry[2] = self.server.tick - 1
        self.admitted += took
        new = len(self.server.completed) - self.done
        self.done += new
        for _ in range(new):
            self.send(full_chunks)


def setup(ctx, clog) -> None:
    import jax.numpy as jnp

    from repro.launch.serve_dfr import DFRServer

    config, mix = ctx.config, ctx.mix
    t0 = time.perf_counter()
    ctx.pool = generate.serve_pool(config, mix, ctx.seed)
    ctx.mask = generate.mask(config)
    ctx.split["data_s"] = time.perf_counter() - t0

    slots = int(config["serve"]["slots"])
    server = DFRServer(session_config(config), slots)
    server.mask = jnp.asarray(ctx.mask)
    if ctx.fault is not None:
        server._step = _faulty_step(ctx.fault)
    t0 = time.perf_counter()
    c0 = clog.backend_seconds()
    server.warmup()
    warm = time.perf_counter() - t0
    compile_s = clog.backend_seconds() - c0
    ctx.split["compile_or_cache_load_s"] = compile_s
    ctx.split["first_call_s"] = warm - compile_s
    ctx.server = server
    ctx.clients = Clients(server, ctx.pool, mix)
    ctx.shape = dict(slots=slots, chunk=server.cfg.chunk_k,
                     n=int(config["n_nodes"]), c=1)


def window(ctx) -> None:
    import jax

    server, clients = ctx.server, ctx.clients
    full = int(ctx.mix["stream_chunks"])
    tick_clock, t0 = [], time.perf_counter()
    while time.perf_counter() - t0 < ctx.seconds:
        queued = len(server.queue)
        a = time.perf_counter()
        with jax.profiler.TraceAnnotation("serve.tick"):
            server.step()
        tick_clock.append(time.perf_counter() - a)
        clients.after_tick(queued, full)
    window_s = time.perf_counter() - t0
    ctx.tick_clock = np.asarray(tick_clock)
    ctx.tick_seconds = np.asarray(server.tick_seconds)

    every, washout = server.cfg.refresh_every, int(ctx.config["washout"])
    periods = fit_periods = solves = 0
    for req, _, admit in clients.sent:
        if admit is None:
            continue
        served = -(-req.pos // ctx.shape["chunk"])
        periods += req.pos
        fit_periods += max(req.pos - washout, 0)
        solves += int(np.sum((admit + np.arange(served)) % every == 0))
    admitted = sum(1 for _, _, admit in clients.sent if admit is not None)
    ctx.record.update(
        ticks=len(tick_clock), window_s=window_s, periods=periods,
        fit_periods=fit_periods, refresh_solves=solves,
        completed=len(server.completed), attempted=admitted,
        failed=len(server.evicted) + server.counters["quarantine_events"])


def _sample(ctx):
    """Completed requests to replay: drawn from the seed, a full-length
    stream among them."""
    done = [e for e in ctx.clients.sent if e[0].done and e[2] is not None]
    if not done:
        return []
    rng = np.random.default_rng([ctx.seed % 2**64, 11])
    size = min(int(ctx.limits_sample), len(done))
    pick = [done[i] for i in rng.choice(len(done), size=size, replace=False)]
    longest = max(done, key=lambda e: len(e[0].j))
    if all(len(e[0].j) < len(longest[0].j) for e in pick):
        pick[0] = longest
    return pick


NAN_NUMBERS = ("ser_gap", "mean_gap", "not_finite")


def compare(config: dict, streams, served, y_ref) -> dict:
    """The numbers of the check for the served predictions ``served`` (one
    array a stream) of ``streams`` ((j, y, admission tick) each), against
    the reference's replay ``y_ref`` of each stream from its admission tick.

    Scored are the periods past the washout (a stream no longer than the
    washout has none):

    * ``ser_gap`` -- the gap between the symbol error rates of the served
      and the replayed predictions, over every sampled stream;
    * ``mean_gap`` -- the median over streams of the mean |y - y_ref|;
    * ``not_finite`` -- non-finite served predictions.
    """
    sym = np.asarray(reference.SYMBOLS, np.float32)
    washout = int(config["washout"])

    def decided(y):
        return sym[np.argmin(np.abs(y[:, None] - sym[None, :]), axis=1)]

    means, not_finite = [], 0
    err_p = err_r = scored = 0
    for (_, target, _), yp, yr in zip(streams, served, y_ref):
        if yp.shape != yr.shape:
            return {k: float("nan") for k in NAN_NUMBERS}
        not_finite += int(np.sum(~np.isfinite(yp)))
        yp, yr, tg = yp[washout:], yr[washout:], target[washout:]
        if not len(tg):
            continue
        means.append(float(np.mean(np.abs(yp - yr))))
        err_p += int(np.sum(decided(yp) != tg))
        err_r += int(np.sum(decided(yr) != tg))
        scored += len(tg)
    if not means:
        return {k: float("nan") for k in NAN_NUMBERS}
    return {"ser_gap": abs(err_p - err_r) / scored,
            "mean_gap": float(np.median(means)),
            "not_finite": float(not_finite)}


def variant_served(variant: str, config: dict, mask, streams) -> list:
    """Predictions that stand in for the program's: ``reference_high``, the
    control (the reference with its readout at ``Precision.HIGH``);
    ``reference_device``, the reference computed on the default device (the
    accelerator), a witness of its own arithmetic."""
    if variant == "reference_high":
        return reference.sessions(config, mask, streams, precision="high")
    if variant == "reference_device":
        import jax

        return reference.sessions(config, mask, streams, device=jax.devices()[0])
    raise ValueError(f"unknown variant {variant!r}")


def check(ctx) -> dict:
    """Every served prediction of the sampled streams against the
    reference's replay (``compare``)."""
    pick = _sample(ctx)
    if not pick:
        return {k: float("nan") for k in NAN_NUMBERS}
    streams = [(req.j, req.y, admit) for req, _, admit in pick]
    if ctx.variant is not None:
        served = variant_served(ctx.variant, ctx.config, ctx.mask, streams)
    else:
        served = [np.concatenate(req.y_hat) if req.y_hat else np.zeros(0, np.float32)
                  for req, _, _ in pick]
    numbers = compare(ctx.config, streams, served,
                      reference.sessions(ctx.config, ctx.mask, streams))
    ctx.log("numbers: " + json.dumps(numbers) + f" ({len(streams)} sampled streams,"
            f" {sum(len(s[0]) for s in streams)} periods)")
    return numbers

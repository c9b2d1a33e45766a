"""DuplexScheduler — the co-scheduling simulation engine (CXLAimPod §4-§5).

Discrete-time (1 us/step) simulation, fully jit'd as a single
``jax.lax.scan``:

  state_t = (backlogs, channel state, policy state)
  1. arrivals[t] append to per-stream backlogs (offered work).
  2. ``policy.schedule`` assigns run weights w (CPU-slot shares).
  3. running streams offer demand: each stream drains its backlog at
     ``drain_rate * w_i``, split by the backlog's direction composition.
  4. the channel (``channel_step``) moves what its duplex/half-duplex
     capacity allows; a *migration tax* proportional to weight reallocation
     models cache disruption from task migration (§5.2's hysteresis
     rationale) and is charged against capacity.
  5. moved bytes are rationed back to streams pro-rata; backlogs shrink;
     ``policy.update`` receives feedback.

Outputs: achieved bandwidth (total and per direction), utilization series,
switch counts, migration volume, backlog (latency proxy via Little's law).

This engine is used three ways:
  * microbenchmark reproduction (benchmarks/characterization, microbench),
  * the paper's Fig. 6 LLM phases (benchmarks/llm_inference),
  * planning real duplex offload transfers (core/offload.py) — the same
    policy decides the page-in/page-out interleave order.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import channel as channel_lib
from repro.core import policies as policies_lib
from repro.core import requests as requests_lib
from repro.core.channel import ChannelModel
from repro.core.policies import Policy, PolicyParams


@dataclasses.dataclass(frozen=True)
class SimConfig:
    steps: int = 2048
    drain_rate_factor: float = 2.0   # per-stream CPU drain cap vs offered rate
    migration_tax: float = 0.02      # capacity fraction lost per unit L1 move
    sequential: bool = False
    seed: int = 0
    # Discrete CPU slots: each of the n_slots "cores" runs exactly ONE
    # stream per step (the paper's setting — `duplex_select_cpu` exists
    # because a core's traffic is its running task's unidirectional
    # pattern). False = idealized processor sharing (every stream runs
    # fractionally; aggregate traffic self-balances and the duplex
    # opportunity largely disappears — kept as an ablation).
    discrete_slots: bool = True
    # Closed loop (the paper's saturation benchmarks): each stream is a
    # byte TAPE consumed at drain rate whenever scheduled — phases are
    # progress-driven, so a scheduled-ahead worker enters its write phase
    # early (what makes pipeline priming possible). False = open loop:
    # requests arrive on the wall clock (latency-oriented workloads).
    closed_loop: bool = True


class SimState(NamedTuple):
    exec_bytes: jnp.ndarray      # (S,) program progress per stream (bytes)
    chan: channel_lib.ChannelState
    policy_state: object
    prev_w: jnp.ndarray          # (S,)
    prev_util: jnp.ndarray       # scalar


class SimResult(NamedTuple):
    moved_read: jnp.ndarray      # (T,) bytes/step
    moved_write: jnp.ndarray     # (T,)
    utilization: jnp.ndarray     # (T,)
    backlog_total: jnp.ndarray   # (T,) bytes outstanding
    weights: jnp.ndarray         # (T, S)
    migration: jnp.ndarray       # (T,)
    switches: jnp.ndarray        # scalar (half-duplex turnarounds charged)

    # -- derived metrics ----------------------------------------------------
    def achieved_gbps(self) -> jnp.ndarray:
        # bytes/us == 1e-3 GB/s^-1 -> GB/s = bytes_per_step * 1e-3
        return (jnp.mean(self.moved_read + self.moved_write)) * 1.0e-3

    def read_gbps(self) -> jnp.ndarray:
        return jnp.mean(self.moved_read) * 1.0e-3

    def write_gbps(self) -> jnp.ndarray:
        return jnp.mean(self.moved_write) * 1.0e-3

    def mean_backlog_bytes(self) -> jnp.ndarray:
        return jnp.mean(self.backlog_total)

    def p99_backlog_bytes(self) -> jnp.ndarray:
        return jnp.percentile(self.backlog_total, 99.0)

    def mean_latency_us(self) -> jnp.ndarray:
        """Little's law: L = lambda * W  =>  W = backlog / throughput."""
        thr = jnp.maximum(jnp.mean(self.moved_read + self.moved_write), 1e-9)
        return jnp.mean(self.backlog_total) / thr

    def p99_latency_us(self) -> jnp.ndarray:
        thr = jnp.maximum(jnp.mean(self.moved_read + self.moved_write), 1e-9)
        return jnp.percentile(self.backlog_total, 99.0) / thr


def _interp_columns(C, CT, e):
    """Piecewise-linear value of cumulative C at executed-byte position e.

    C, CT: (T+1, S) per-stream prefix sums (direction / total); e: (S,).
    Within a step, arrivals are consumed at that step's r/w composition —
    this is what encodes *program order*: a stream executes its requests in
    the order its program issued them, so a delayed read phase is executed
    later (still unidirectional), never blended with the next write phase.
    """
    def one(ct_col, c_col, ei):
        j = jnp.clip(jnp.searchsorted(ct_col, ei, side="right") - 1,
                     0, ct_col.shape[0] - 2)
        seg = ct_col[j + 1] - ct_col[j]
        frac = jnp.where(seg > 0, (ei - ct_col[j]) / jnp.maximum(seg, 1e-9),
                         0.0)
        frac = jnp.clip(frac, 0.0, 1.0)
        return c_col[j] + frac * (c_col[j + 1] - c_col[j])

    return jax.vmap(one, in_axes=(1, 1, 0))(CT, C, e)


@functools.partial(jax.jit,
                   static_argnames=("policy", "sim", "channel", "params"))
def _simulate_jit(arrivals: jnp.ndarray,
                  drain_caps: jnp.ndarray,
                  hint_rf: jnp.ndarray,
                  hint_priority: jnp.ndarray,
                  hint_opt_in: jnp.ndarray,
                  opt_r: jnp.ndarray,
                  *,
                  policy: Policy,
                  params: PolicyParams,
                  channel: ChannelModel,
                  sim: SimConfig) -> SimResult:
    T = int(sim.steps)
    S = arrivals.shape[1]
    chan_params = channel_lib.channel_params(channel, sim.sequential)
    cap_total = chan_params.read_cap + chan_params.write_cap

    # per-stream cumulative program schedules (program-order execution).
    # The tape may be longer than the simulated horizon (closed loop:
    # leaders may execute ahead of the wall clock).
    zero = jnp.zeros((1, S), jnp.float32)
    CR = jnp.concatenate([zero, jnp.cumsum(arrivals[:, :, 0], 0)], 0)
    CW = jnp.concatenate([zero, jnp.cumsum(arrivals[:, :, 1], 0)], 0)
    CT = CR + CW                                        # (tape+1, S)

    init = SimState(
        exec_bytes=jnp.zeros((S,), jnp.float32),
        chan=channel_lib.init_channel_state(),
        policy_state=policy.init(params, S),
        prev_w=jnp.zeros((S,), jnp.float32),
        prev_util=jnp.float32(0.0),
    )

    def step(state: SimState, inputs):
        t, arr = inputs
        e = state.exec_bytes
        exec_bound = CT[-1] if sim.closed_loop else CT[t + 1]
        # what each stream's program has issued so far but not executed
        done_r = _interp_columns(CR, CT, e)
        done_w = _interp_columns(CW, CT, e)
        backlog_r = jnp.maximum(CR[t + 1] - done_r, 0.0)
        backlog_w = jnp.maximum(CW[t + 1] - done_w, 0.0)

        # head-of-line program segment (what runs next if dispatched)
        e_head = jnp.minimum(e + drain_caps, exec_bound)
        head_r = _interp_columns(CR, CT, e_head) - done_r
        head_w = _interp_columns(CW, CT, e_head) - done_w
        if sim.closed_loop:
            # closed loop: a worker always has its tape to run
            backlog_r = jnp.maximum(backlog_r, head_r)
            backlog_w = jnp.maximum(backlog_w, head_w)

        obs = policies_lib.Obs(
            step=t,
            backlog_read=backlog_r,
            backlog_write=backlog_w,
            arrival_read=arr[:, 0],
            arrival_write=arr[:, 1],
            head_read=head_r,
            head_write=head_w,
            prev_weights=state.prev_w,
            prev_util=state.prev_util,
            opt_r=opt_r,
            duplex=chan_params.duplex,
            hint_rf=hint_rf,
            hint_priority=hint_priority,
            hint_opt_in=hint_opt_in,
        )
        pstate, w = policy.schedule(params, state.policy_state, obs)

        if sim.discrete_slots:
            # Hard dispatch: top-n_slots streams by policy weight run this
            # step (weight 1), everything else waits. A rotating epsilon
            # breaks ties deterministically, so equal-weight policies
            # (cfs) degrade to direction-oblivious round-robin — the
            # paper's baseline behavior.
            k = max(1, min(S, int(params.n_slots)))
            active = (backlog_r + backlog_w) > 0.0
            eps = 1e-6 * (((jnp.arange(S) + t) % S).astype(jnp.float32)
                          / S)
            w_eff = jnp.where(active, w + eps, -1.0)
            kth = jax.lax.top_k(w_eff, k)[0][-1]
            w = ((w_eff >= kth) & active).astype(jnp.float32)

        # running streams execute their next program segment (in order)
        budget = w * drain_caps
        e_try = jnp.minimum(e + budget, exec_bound)
        want_r = _interp_columns(CR, CT, e_try) - done_r
        want_w = _interp_columns(CW, CT, e_try) - done_w

        # migration tax: reallocating run slots disrupts caches; model as a
        # transient loss of channel capacity this step.
        mig = policies_lib.migration_volume(state.prev_w, w)
        tax = jnp.clip(1.0 - sim.migration_tax * mig, 0.5, 1.0)

        chan, moved_r_tot, moved_w_tot = channel_lib.channel_step(
            chan_params, state.chan, jnp.sum(want_r) * tax,
            jnp.sum(want_w) * tax)

        # ration actual service back to streams pro-rata to demand
        ratio_r = moved_r_tot / jnp.maximum(jnp.sum(want_r), 1e-9)
        ratio_w = moved_w_tot / jnp.maximum(jnp.sum(want_w), 1e-9)
        moved_r = want_r * jnp.minimum(ratio_r, 1.0)
        moved_w = want_w * jnp.minimum(ratio_w, 1.0)
        e = jnp.minimum(e + moved_r + moved_w, exec_bound)

        total_backlog = jnp.sum(jnp.maximum(CT[t + 1] - e, 0.0))
        chan_util = (moved_r_tot + moved_w_tot) / jnp.maximum(cap_total,
                                                              1e-9)
        # Algorithm 1's oversubscription test uses *CPU* utilization
        # (running slots / cores), not channel utilization.
        cpu_util = jnp.sum(w) / params.n_slots
        pstate = policy.update(params, pstate,
                               policies_lib.Feedback(moved_r, moved_w,
                                                     cpu_util))

        new_state = SimState(e, chan, pstate, w, cpu_util)
        out = (moved_r_tot, moved_w_tot, chan_util, total_backlog, w, mig)
        return new_state, out

    final, outs = jax.lax.scan(
        step, init, (jnp.arange(T, dtype=jnp.int32), arrivals[:T]))
    moved_r, moved_w, util, backlog, weights, mig = outs
    return SimResult(moved_r, moved_w, util, backlog, weights, mig,
                     final.chan.switches)


def simulate(channel: ChannelModel,
             specs: list[requests_lib.StreamSpec],
             policy: Policy | str,
             params: PolicyParams | None = None,
             sim: SimConfig | None = None) -> SimResult:
    """Run one policy over one channel for a list of stream specs."""
    if isinstance(policy, str):
        policy = policies_lib.get_policy(policy)
    params = params or PolicyParams()
    sim = sim or SimConfig()

    # closed loop: the tape extends past the horizon so leaders can run
    # ahead of the wall clock (drain cap bounds how far).
    tape_steps = (int(sim.steps * (sim.drain_rate_factor + 1.0))
                  if sim.closed_loop else sim.steps)
    arrivals = requests_lib.generate(specs, tape_steps, sim.seed)
    offered = jnp.asarray([s.offered_gbps * 1e3 for s in specs],
                          jnp.float32)
    drain_caps = offered * sim.drain_rate_factor
    hint_rf = requests_lib.hint_read_fractions(specs)
    hint_priority = jnp.asarray(
        [s.resolved_hint().resolved().priority for s in specs], jnp.float32)
    hint_opt_in = jnp.asarray(
        [s.resolved_hint().resolved().duplex_opt_in for s in specs])

    opt = channel_lib.duplex_benefit(channel, sim.sequential)
    opt_r = jnp.float32(opt["peak_read_fraction"])

    return _simulate_jit(arrivals, drain_caps, hint_rf, hint_priority,
                         hint_opt_in, opt_r, policy=policy, params=params,
                         channel=channel, sim=sim)


def compare_policies(channel: ChannelModel,
                     specs: list[requests_lib.StreamSpec],
                     policy_names: tuple[str, ...] = ("cfs", "timeseries"),
                     params: PolicyParams | None = None,
                     sim: SimConfig | None = None) -> dict[str, dict]:
    """A/B harness: run several policies on identical arrivals and report."""
    out = {}
    for name in policy_names:
        res = simulate(channel, specs, name, params, sim)
        out[name] = {
            "gbps": float(res.achieved_gbps()),
            "read_gbps": float(res.read_gbps()),
            "write_gbps": float(res.write_gbps()),
            "mean_latency_us": float(res.mean_latency_us()),
            "p99_latency_us": float(res.p99_latency_us()),
            "switches": int(res.switches),
            "migration": float(jnp.sum(res.migration)),
        }
    return out


def improvement(results: dict[str, dict], test: str = "timeseries",
                base: str = "cfs", metric: str = "gbps") -> float:
    return results[test][metric] / max(results[base][metric], 1e-9) - 1.0

"""LPDDR4-3200 dual-channel DRAM timing model with an FR-FCFS controller.

Paper Section 2/4 memory system: dual-channel LPDDR4-3200, single rank,
8 banks, BL8, tCAS-tRCD-tRP = 15-15-15.  The controller has a *small*
pending-queue window per channel (the realistic baseline — row-hit-first
scheduling inside a limited lookahead).  MARS's whole premise is that this
window is too small to recover locality that multi-level arbitration
destroyed, while naively growing it is impractical.

Model (documented simplifications):
  * unit = DRAM command clock @ 1.6 GHz (LPDDR4-3200 => 2 transfers/clock)
  * one 64B line = BL8 burst = 4 data-bus clocks; per-channel peak
    bandwidth = 64 B / 4 clk = 25.6 GB/s, 51.2 GB/s total
  * row buffer 2 KB/bank/channel (32 lines); a 4 KB OS page maps to one
    (bank, row) pair in each channel -> requests of one page on one channel
    share a row, exactly the paper's memory-map-agnostic locality argument
  * row hit:   data start >= max(bus_free, bank_ready)
    row miss:  PRE (tRP, if a row was open) + ACT (ACT->CAS tRCD) off the
    critical path of other banks' transfers; tFAW (max 4 ACTs / 40 clk) and
    tRRD (8 clk) limit activate rate — these are what make a low CAS/ACT
    stream bandwidth-bound
  * read<->write direction switches pay a bus-turnaround penalty
    (tWTR / tRTW), so mixed-direction streams cap below pure-stream peak

Everything is a ``jax.lax.scan`` over served requests (one request per
step, FR-FCFS pick inside the window), fully jittable.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class DramConfig:
    n_channels: int = 2
    n_banks: int = 8
    lines_per_row: int = 32     # 2KB row buffer / 64B lines
    t_cas: int = 15
    t_rcd: int = 15
    t_rp: int = 15
    t_burst: int = 4            # BL8 @ 2 transfers/clock
    t_ccd: int = 4
    t_rrd: int = 8
    t_faw: int = 40
    t_wtr: int = 12             # write->read bus turnaround
    t_rtw: int = 8              # read->write bus turnaround
    window: int = 32            # MC pending-queue entries per channel
    clock_ghz: float = 1.6
    line_bytes: int = 64

    @property
    def peak_gbps(self) -> float:
        return self.n_channels * self.line_bytes / self.t_burst * self.clock_ghz


@dataclasses.dataclass(frozen=True)
class DramResult:
    cycles: int
    n_requests: int
    n_act: int
    achieved_gbps: float
    bus_utilization: float
    cas_per_act: float
    per_channel_cycles: tuple


def split_channels(addr: np.ndarray, cfg: DramConfig):
    """Address map: channel striped at 128B; within a channel the local
    line id is contiguous per page (see module docstring)."""
    a = np.asarray(addr, np.int64)
    if cfg.n_channels & (cfg.n_channels - 1):
        raise ValueError(
            f"n_channels must be a power of two, got {cfg.n_channels}: the "
            "128B channel stripe extracts the channel id as a bit field")
    ch_bits = int(np.log2(cfg.n_channels))
    ch = (a >> 1) & (cfg.n_channels - 1)
    local = ((a >> (1 + ch_bits)) << 1) | (a & 1)
    return ch, local


def _decode(local: jnp.ndarray, cfg: DramConfig):
    col = local % cfg.lines_per_row
    row = local // (cfg.lines_per_row * cfg.n_banks)
    # bank-address hashing (XOR-fold ALL row/page bits into the bank
    # select) — standard MC practice to break stride-induced bank
    # conflicts at any power-of-two stride
    k = max(1, (cfg.n_banks - 1).bit_length())
    page = local // cfg.lines_per_row
    b = page
    x = page >> k
    for _ in range(max(1, (31 + k - 1) // k)):
        b = b ^ x
        x = x >> k
    bank = b % cfg.n_banks
    return col, bank, row


def decode_lines(local: np.ndarray, cfg: DramConfig):
    """Public (col, bank, row) decode of channel-local line ids.

    Pure arithmetic — works element-wise on numpy or jax arrays alike.
    This is the exact map the FR-FCFS controller uses, exported so the
    live open-row model in ``obs/rowsim.py`` shares it instead of
    re-deriving the bank hash (one address map, one place).
    """
    return _decode(local, cfg)


class _ChState(NamedTuple):
    win_local: jnp.ndarray   # int32[W] local line ids
    win_arr: jnp.ndarray     # int32[W] arrival order
    win_wr: jnp.ndarray      # bool[W] write flag
    win_valid: jnp.ndarray   # bool[W]
    cursor: jnp.ndarray      # int32 next input idx
    open_row: jnp.ndarray    # int32[B], -1 closed
    bank_ready: jnp.ndarray  # int32[B] earliest data start on open row
    bus_free: jnp.ndarray    # int32
    act_hist: jnp.ndarray    # int32[4] ring of last ACT times (for tFAW)
    act_ptr: jnp.ndarray     # int32
    last_act: jnp.ndarray    # int32 (for tRRD)
    last_dir: jnp.ndarray    # int32 0=read 1=write
    n_act: jnp.ndarray       # int32
    t_end: jnp.ndarray       # int32 latest data end


_BIG = np.int32(1 << 29)   # numpy: importing must not start a jax backend


@functools.partial(jax.jit, static_argnums=(2, 3))
def _run_channel(local: jnp.ndarray, is_write: jnp.ndarray, n: int,
                 cfg: DramConfig):
    W, B = cfg.window, cfg.n_banks
    pad = max(0, W - n)
    if pad:
        local = jnp.concatenate([local, jnp.zeros(pad, jnp.int32)])
        is_write = jnp.concatenate([is_write, jnp.zeros(pad, bool)])
    loc_pad = local

    init = _ChState(
        win_local=loc_pad[:W],
        win_arr=jnp.arange(W, dtype=jnp.int32),
        win_wr=is_write[:W],
        win_valid=jnp.arange(W) < n,
        cursor=jnp.int32(W),
        open_row=jnp.full(B, -1, jnp.int32),
        bank_ready=jnp.zeros(B, jnp.int32),
        bus_free=jnp.zeros((), jnp.int32),
        act_hist=jnp.full(4, -_BIG, jnp.int32),
        act_ptr=jnp.zeros((), jnp.int32),
        last_act=-_BIG * jnp.ones((), jnp.int32),
        last_dir=jnp.zeros((), jnp.int32),
        n_act=jnp.zeros((), jnp.int32),
        t_end=jnp.zeros((), jnp.int32),
    )

    def step(s: _ChState, _):
        col, bank, row = _decode(s.win_local, cfg)
        hit = s.win_valid & (s.open_row[bank] == row)
        # FR-FCFS: row hits first, oldest first; invalid slots never chosen
        key = jnp.where(s.win_valid, jnp.where(hit, 0, _BIG) + s.win_arr, 2 * _BIG)
        j = jnp.argmin(key)
        valid = s.win_valid[j]
        b, r = bank[j], row[j]
        is_hit = hit[j]

        was_open = s.open_row[b] >= 0
        # activate path (off other banks' data critical path)
        act_t = jnp.maximum(
            s.bank_ready[b] + jnp.where(was_open, cfg.t_rp, 0),
            jnp.maximum(s.act_hist[s.act_ptr] + cfg.t_faw,
                        s.last_act + cfg.t_rrd))
        row_ready = act_t + cfg.t_rcd
        # read<->write turnaround occupies the bus
        dirn = s.win_wr[j].astype(jnp.int32)
        turn = jnp.where(dirn == s.last_dir, 0,
                         jnp.where(dirn == 1, cfg.t_rtw, cfg.t_wtr))
        bus_avail = s.bus_free + turn
        start = jnp.where(is_hit,
                          jnp.maximum(bus_avail, s.bank_ready[b]),
                          jnp.maximum(bus_avail, row_ready))
        end = start + cfg.t_burst

        did_act = valid & ~is_hit
        new = s._replace(
            open_row=s.open_row.at[b].set(jnp.where(did_act, r, s.open_row[b])),
            bank_ready=s.bank_ready.at[b].set(
                jnp.where(valid, start + cfg.t_ccd, s.bank_ready[b])),
            bus_free=jnp.where(valid, end, s.bus_free),
            act_hist=s.act_hist.at[s.act_ptr].set(
                jnp.where(did_act, act_t, s.act_hist[s.act_ptr])),
            act_ptr=jnp.where(did_act, (s.act_ptr + 1) % 4, s.act_ptr),
            last_act=jnp.where(did_act, act_t, s.last_act),
            last_dir=jnp.where(valid, dirn, s.last_dir),
            n_act=s.n_act + did_act.astype(jnp.int32),
            t_end=jnp.maximum(s.t_end, jnp.where(valid, end, 0)),
        )
        # refill slot j from the input stream
        have_next = new.cursor < n
        nxt = local[jnp.minimum(new.cursor, n - 1)] if n else jnp.int32(0)
        nxt_wr = is_write[jnp.minimum(new.cursor, n - 1)] if n else jnp.bool_(False)
        new = new._replace(
            win_local=new.win_local.at[j].set(
                jnp.where(valid & have_next, nxt, new.win_local[j])),
            win_arr=new.win_arr.at[j].set(
                jnp.where(valid & have_next, new.cursor, new.win_arr[j])),
            win_wr=new.win_wr.at[j].set(
                jnp.where(valid & have_next, nxt_wr, new.win_wr[j])),
            win_valid=new.win_valid.at[j].set(valid & have_next),
            cursor=new.cursor + (valid & have_next).astype(jnp.int32),
        )
        return new, is_hit & valid

    final, hits = jax.lax.scan(step, init, None, length=n)
    return final.t_end, final.n_act, hits.sum()


def simulate(addr: np.ndarray, cfg: DramConfig | None = None,
             is_write: np.ndarray | None = None) -> DramResult:
    """Serve ``addr`` (64B-line ids, already in arrival order) and report
    achieved bandwidth + CAS/ACT."""
    cfg = cfg or DramConfig()
    ch, local = split_channels(addr, cfg)
    if is_write is None:
        is_write = np.zeros(len(addr), bool)
    is_write = np.asarray(is_write, bool)
    t_ends, n_acts = [], []
    n_total = len(addr)
    for c in range(cfg.n_channels):
        sel = ch == c
        l = jnp.asarray(local[sel], jnp.int32)
        n = int(l.shape[0])
        if n == 0:
            t_ends.append(0)
            n_acts.append(0)
            continue
        t_end, n_act, _ = _run_channel(l, jnp.asarray(is_write[sel]), n, cfg)
        t_ends.append(int(t_end))
        n_acts.append(int(n_act))
    cycles = max(t_ends) if t_ends else 0
    n_act = sum(n_acts)
    secs = cycles / (cfg.clock_ghz * 1e9) if cycles else 1.0
    gbps = n_total * cfg.line_bytes / secs / 1e9 if cycles else 0.0
    return DramResult(
        cycles=cycles, n_requests=n_total, n_act=max(n_act, 1),
        achieved_gbps=gbps,
        bus_utilization=gbps / cfg.peak_gbps if cycles else 0.0,
        cas_per_act=n_total / max(n_act, 1),
        per_channel_cycles=tuple(t_ends),
    )

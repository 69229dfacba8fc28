"""The cell's traffic, fed to the running serving driver.

``Load.hook`` is installed as the ``ServingDriver``'s ``on_tick`` hook
(``driver.on_tick``, called at every turn of its loop, under its lock,
on its own thread): it submits to the pool every request that is due,
runs the scheduler's tick on its deployed cadence, and registers a pool
listener per request that stamps each event on the host clock. The pool
steps on as the driver steps it. Nothing outside the driver's thread
touches the pool while it serves.

Open loop: each request is submitted at the driver's first turn after
its due time, and its latency counts from that due time, so a long step
also delays the requests that fall due during it. Closed loop: each
client submits its next request at the driver's first turn after the
previous one has ended. Requests submitted inside the window are
measured. Load goes on after the window until they have all ended (or
until the drain cap), so they finish under the load they were measured
in.

This client work (submitting, the listeners' stamps and records) runs on
the driver's thread, so it adds to the host time between steps that the
system under test would spend without it. Its time inside the window is
measured and printed (``Load.client_cost``); the scheduler's tick, which
the hook only calls, is the system's and is counted apart.

A record per request holds what its client would have seen: when it was
due and submitted, when its ``prefill`` event, first token and terminal
event came, its tokens, and any break in the event protocol (token
indices out of order, a finished event whose tokens differ from the
streamed ones, fewer tokens than asked without truncation).
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional

import traffic

#: the first request is due this long after the load starts
LEAD_S = 0.3
#: the scheduler's wall-clock decision interval, as ``serve_http`` runs it
CONTROL_S = 0.5
TERMINAL = ("finished", "rejected", "cancelled")


def new_record(req: traffic.Request, phase: str, due: float
               ) -> Dict[str, Any]:
    return {"idx": req.idx, "phase": phase, "due": due, "sent": None,
            "status": "unfinished", "t_prefill": None, "t_first": None,
            "t_finish": None, "n_tokens": 0, "n_win_tokens": 0,
            "prompt_len": len(req.prompt), "max_new": req.max_new,
            "prompt_in_window": False, "truncated": False,
            "protocol": None, "tokens": []}


class Load:
    def __init__(self, mix: Dict[str, Any], load: Dict[str, Any],
                 reqs: List[traffic.Request], model: str, window_s: float):
        self.open = mix["loop"] == "open"
        self.reqs, self.model = reqs, model
        #: the scheduler's tick, set by ``System.serve``
        self.tick: Optional[Callable] = None
        self.warm_s, self.window_s = float(load["warm_s"]), float(window_s)
        self.free = int(load.get("clients", 0))
        self.records: List[Dict[str, Any]] = []
        self.stop = False
        self.next = 0
        self.t0 = self.ws = self.we = None
        self.next_tick = 0.0
        #: [calls, seconds] inside the window: the hook (less the
        #: scheduler's tick), the tick, and the listeners' events
        self.cost = {k: [0, 0.0] for k in ("hook", "tick", "events")}
        #: open loop: one past the last window request (they are in order)
        self.window_end = max((r.idx + 1 for r in reqs
                               if r.phase == "window"), default=0)

    def start(self) -> None:
        """Fix the window on the host clock; the hook sends from now."""
        self.t0 = time.perf_counter() + LEAD_S
        self.ws = self.t0 + self.warm_s
        self.we = self.ws + self.window_s
        self.next_tick = self.t0

    # ---- on the driver's thread ------------------------------------------
    def hook(self, pool) -> None:
        now = time.perf_counter()
        if self.t0 is None or now < self.t0:
            return
        if not self.stop:
            if self.open:
                while self.next < len(self.reqs) and \
                        self.ws + self.reqs[self.next].due_s <= now:
                    r = self.reqs[self.next]
                    self._submit(pool, r, r.phase, self.ws + r.due_s, now)
                    self.next += 1
            else:
                while self.free > 0 and self.next < len(self.reqs):
                    phase = "warm" if now < self.ws else \
                        "window" if now < self.we else "cool"
                    self.free -= 1
                    self._submit(pool, self.reqs[self.next], phase, now, now)
                    self.next += 1
        tick_s = 0.0
        if self.tick is not None and now >= self.next_tick:
            self.next_tick = now + CONTROL_S
            t = time.perf_counter()
            self.tick(pool)
            tick_s = time.perf_counter() - t
            self._charge("tick", t, tick_s)
        self._charge("hook", now, time.perf_counter() - now - tick_s)

    def _charge(self, what: str, t0: float, seconds: float) -> None:
        if self.ws <= t0 < self.we:
            c = self.cost[what]
            c[0] += 1
            c[1] += seconds

    def _submit(self, pool, req, phase, due, now) -> None:
        rec = new_record(req, phase, due)
        rec["sent"] = now
        self.records.append(rec)
        try:
            rid = pool.submit(self.model, req.prompt, slo_ms=req.slo_ms,
                              max_new_tokens=req.max_new)
        except ValueError as e:
            rec["status"] = "error"
            rec["protocol"] = f"submit refused: {e}"
            self._ended()
            return
        pool.add_listener(rid, lambda ev, rec=rec: self._event(rec, ev))

    def _ended(self) -> None:
        if not self.open:
            self.free += 1

    def _event(self, rec: Dict[str, Any], ev: Dict[str, Any]) -> None:
        now = time.perf_counter()
        kind = ev["event"]
        if kind == "prefill" and rec["t_prefill"] is None:
            rec["t_prefill"] = now
        elif kind == "token":
            toks = rec["tokens"]
            if ev["index"] != len(toks):
                rec["protocol"] = (f"token index {ev['index']}, expected "
                                   f"{len(toks)}")
            toks.append(int(ev["token"]))
            inside = self.ws <= now < self.we
            if rec["t_first"] is None:
                rec["t_first"] = now
                rec["prompt_in_window"] = inside
            rec["n_win_tokens"] += inside
        elif kind in TERMINAL:
            rec["t_finish"] = now
            rec["status"] = kind
            rec["n_tokens"] = len(rec["tokens"])
            if kind == "finished":
                rec["truncated"] = bool(ev.get("truncated"))
                if list(ev.get("tokens", [])) != rec["tokens"]:
                    rec["protocol"] = ("finished tokens differ from the "
                                       "streamed ones")
                elif not rec["truncated"] \
                        and rec["n_tokens"] != rec["max_new"]:
                    rec["protocol"] = (f"{rec['n_tokens']} tokens, asked "
                                       f"{rec['max_new']}")
            self._ended()
        self._charge("events", now, time.perf_counter() - now)

    # ---- on the caller's thread ------------------------------------------
    def _window_open(self) -> bool:
        if self.open and self.next < self.window_end:
            return True
        return any(r["phase"] == "window" and r["status"] == "unfinished"
                   for r in list(self.records))

    def wait(self, drain_cap_s: float, poll_s: float = 0.02) -> None:
        """Block until the window has closed and its requests have all
        ended, or the drain cap has passed; then stop sending."""
        cap = self.we + drain_cap_s
        while time.perf_counter() < self.we:
            time.sleep(poll_s)
        while time.perf_counter() < cap and self._window_open():
            time.sleep(poll_s)
        self.stop = True

    def client_cost(self) -> Dict[str, float]:
        """The client's host time on the driver's thread inside the
        window, and the scheduler's tick beside it."""
        out = {f"{k}_calls": n for k, (n, _) in self.cost.items()}
        out.update({f"{k}_ms": 1e3 * sec for k, (_, sec) in self.cost.items()})
        out["client_share_of_window"] = (
            (self.cost["hook"][1] + self.cost["events"][1]) / self.window_s)
        return out

    def lateness(self) -> Dict[str, float]:
        """How late requests were submitted, against their due time."""
        import numpy as np

        late = np.asarray([r["sent"] - r["due"] for r in list(self.records)
                           if r["sent"] is not None]) * 1e3
        if not len(late):
            return {"n": 0}
        return {"n": int(len(late)), "p50_ms": float(np.percentile(late, 50)),
                "p99_ms": float(np.percentile(late, 99)),
                "max_ms": float(late.max())}

"""Rule lifecycle FSM — analogue of eKuiper's rule.State
(internal/topo/rule/state.go:76-575): Starting/Running/Stopping/Stopped
with a serialized action queue, restart strategy with exponential backoff +
jitter, and per-rule status/metrics aggregation.
"""
from __future__ import annotations

import queue
import random
import threading
from enum import Enum
from typing import Any, Dict, Optional

from ..planner.planner import RuleDef, plan_rule
from ..utils import timex
from ..utils.infra import logger
from .topo import Topo


class RunState(str, Enum):
    STOPPED = "stopped"
    STARTING = "starting"
    RUNNING = "running"
    STOPPING = "stopping"
    STOPPED_BY_ERR = "stopped_by_error"
    # cron/duration rules between activations (reference schedule states,
    # internal/pkg/schedule + def/rule.go:40-42)
    SCHEDULED = "stopped: waiting for next schedule"


class RuleState:
    def __init__(self, rule: RuleDef, store) -> None:
        self.rule = rule
        self.store = store
        self.state = RunState.STOPPED
        self.topo: Optional[Topo] = None
        self.last_error: str = ""
        self.started_at = 0
        self._lock = threading.RLock()
        # worker-spawn guard, SEPARATE from self._lock: _enqueue runs
        # inside timex timer callbacks, which the mock clock fires while
        # holding the clock lock — and self._lock is held elsewhere
        # while reading the clock (_set_state -> flight recorder), so
        # taking self._lock here would close the clock/rule ABBA square
        # utils/lockcheck.py caught on day one (clock orders first)
        self._worker_mu = threading.Lock()
        self._actions: "queue.Queue[str]" = queue.Queue()
        # notified by every state change and at the end of every action
        # (wait_state); a leaf lock: nothing is taken while it is held
        self._settled = threading.Condition()
        self._worker: Optional[threading.Thread] = None
        self._supervisor: Optional[threading.Thread] = None
        self._stop_supervision = threading.Event()
        # schedule options (reference def/rule.go Cron/Duration/...Range)
        from ..utils import cron as cronlib

        self._cron = None
        self._duration_ms = 0
        self._ranges = rule.options.get("cronDatetimeRange") or []
        if rule.options.get("cron"):
            self._cron = cronlib.Cron(str(rule.options["cron"]))
        if rule.options.get("duration"):
            self._duration_ms = cronlib.parse_duration_ms(
                rule.options["duration"])
        if self._cron is not None and self._duration_ms <= 0:
            raise ValueError("cron rules require a duration")
        self._sched_timer = None
        self._sched_gen = 0  # invalidates stale timers after a user stop

    def _set_state(self, st: RunState, reason: str = "") -> None:
        """Every FSM transition goes through here so the flight recorder
        (runtime/events.py) keeps a replayable state history per rule —
        callers hold self._lock or run on the serialized action worker."""
        prev = self.state
        self.state = st
        with self._settled:
            self._settled.notify_all()
        if prev is not st:
            from .events import recorder

            recorder().record(
                "rule_state", rule=self.rule.id,
                severity=("error" if st is RunState.STOPPED_BY_ERR
                          else "info"),
                state=st.value, previous=prev.value,
                **({"reason": reason} if reason else {}))

    # --------------------------------------------------------------- actions
    def start(self) -> None:
        self._enqueue("start")

    def stop(self) -> None:
        self._enqueue("stop")

    def restart(self) -> None:
        self._enqueue("stop")
        self._enqueue("start")

    def wait_state(self, st: RunState, timeout: float) -> bool:
        """Block until the rule RESTS in `st`: the state is `st` and no
        action is queued or running. A transition sets its state first and
        does its work after (topo opened or closed, the schedule's next
        timer armed), so the state alone does not say the work is done."""
        with self._settled:
            return self._settled.wait_for(
                lambda: self.state is st
                and not self._actions.unfinished_tasks, timeout)

    def _enqueue(self, action: str) -> None:
        self._actions.put(action)
        with self._worker_mu:
            if self._worker is None or not self._worker.is_alive():
                self._worker = threading.Thread(
                    target=self._drain_actions, daemon=True,
                    name=f"rule-{self.rule.id}",
                )
                self._worker.start()

    def _drain_actions(self) -> None:
        from ..utils.rulelog import set_rule_context

        set_rule_context(self.rule.id)
        while True:
            try:
                action = self._actions.get(timeout=0.5)
            except queue.Empty:
                return
            try:
                if action == "start":
                    self._do_start()
                elif action == "stop":
                    self._do_stop()
                elif action.startswith("cron_fire:"):
                    self._do_cron_fire(int(action.split(":", 1)[1]))
                elif action.startswith("cron_expire:"):
                    self._do_cron_expire(int(action.split(":", 1)[1]))
            except Exception as exc:
                logger.error("rule %s action %s failed: %s", self.rule.id, action, exc)
                with self._lock:
                    self._set_state(RunState.STOPPED_BY_ERR, reason=str(exc))
                    self.last_error = str(exc)
            finally:
                self._actions.task_done()
                with self._settled:
                    self._settled.notify_all()

    # ------------------------------------------------------------- transitions
    def _do_start(self) -> None:
        with self._lock:
            if self.state in (RunState.RUNNING, RunState.STARTING):
                return
            self._set_state(RunState.STARTING)
        if self._cron is not None:
            self._schedule_next_fire()
            return
        self._open_topo()
        if self._duration_ms > 0:
            # duration-only: run once for the duration, then stop
            gen = self._sched_gen
            self._sched_timer = timex.after(
                self._duration_ms,
                lambda ts: self._enqueue(f"cron_expire:{gen}"))

    def _schedule_next_fire(self) -> None:
        now = timex.now_ms()
        fire_at = self._cron.next_fire_ms(now)
        gen = self._sched_gen
        with self._lock:
            self._set_state(RunState.SCHEDULED)
        self._sched_timer = timex.after(
            fire_at - now, lambda ts: self._enqueue(f"cron_fire:{gen}"))

    def _do_cron_fire(self, gen: int) -> None:
        from ..utils import cron as cronlib

        if gen != self._sched_gen:
            return  # stale timer from before a user stop
        if self.state != RunState.SCHEDULED:
            return
        if not cronlib.in_ranges(timex.now_ms(), self._ranges):
            self._schedule_next_fire()
            return
        self._open_topo()
        self._sched_timer = timex.after(
            self._duration_ms, lambda ts: self._enqueue(f"cron_expire:{gen}"))

    def _do_cron_expire(self, gen: int) -> None:
        if gen != self._sched_gen:
            return
        self._close_topo()
        if self._cron is not None:
            self._schedule_next_fire()
        else:
            with self._lock:
                self._set_state(RunState.STOPPED)

    def _open_topo(self) -> None:
        with self._lock:
            if self.state == RunState.RUNNING:
                return
            self._set_state(RunState.STARTING)
        topo = plan_rule(self.rule, self.store)
        topo.open()
        now = timex.now_ms()  # before the lock — clock orders first
        with self._lock:
            self.topo = topo
            self._set_state(RunState.RUNNING)
            self.started_at = now
            self.last_error = ""
        self._stop_supervision.clear()
        self._supervisor = threading.Thread(
            target=self._supervise, daemon=True,
            name=f"rule-supervisor-{self.rule.id}",
        )
        self._supervisor.start()

    def _close_topo(self) -> None:
        self._stop_supervision.set()
        if self.topo is not None:
            try:
                self.topo.save_state_now()
            except Exception as exc:
                logger.debug("save state on stop failed: %s", exc)
            self.topo.close()
        with self._lock:
            self.topo = None

    def _do_stop(self) -> None:
        with self._lock:
            if self.state == RunState.STOPPED:
                return
            self._set_state(RunState.STOPPING)
        self._sched_gen += 1  # invalidate in-flight schedule timers
        if self._sched_timer is not None:
            self._sched_timer.stop()
            self._sched_timer = None
        self._close_topo()
        with self._lock:
            self._set_state(RunState.STOPPED)

    # ------------------------------------------------------------- supervision
    def _supervise(self) -> None:
        """Watch the topo error channel, apply the restart strategy
        (reference: state.go:498-575 runTopo)."""
        from ..utils.rulelog import set_rule_context

        set_rule_context(self.rule.id)
        opts = self.rule.options.get("restartStrategy", {})
        attempts = int(opts.get("attempts", 0))
        delay = int(opts.get("delay", 1000))
        max_delay = int(opts.get("maxDelay", 30_000))
        multiplier = float(opts.get("multiplier", 2.0))
        jitter = float(opts.get("jitterFactor", 0.1))
        tried = 0
        cur_delay = delay
        while not self._stop_supervision.is_set():
            topo = self.topo
            if topo is None:
                return
            err = topo.wait_error(timeout=0.5)
            if err is None:
                continue
            logger.error("rule %s runtime error: %s", self.rule.id, err)
            with self._lock:
                self.last_error = str(err)
            if tried >= attempts:
                with self._lock:
                    self._set_state(RunState.STOPPED_BY_ERR,
                                    reason=str(err))
                topo.close()
                with self._lock:
                    self.topo = None
                return
            tried += 1
            topo.close()
            sleep_ms = int(cur_delay * (1 + random.uniform(-jitter, jitter)))
            timex.sleep(max(sleep_ms, 0))
            cur_delay = min(int(cur_delay * multiplier), max_delay)
            try:
                new_topo = plan_rule(self.rule, self.store)
                new_topo.open()
                with self._lock:
                    self.topo = new_topo
                    self._set_state(RunState.RUNNING,
                                    reason="restart strategy")
            except Exception as exc:
                with self._lock:
                    self._set_state(RunState.STOPPED_BY_ERR,
                                    reason=str(exc))
                    self.last_error = str(exc)
                return

    # ----------------------------------------------------------------- status
    def status(self) -> Dict[str, Any]:
        with self._lock:
            out: Dict[str, Any] = {
                "status": self.state.value,
            }
            if self.last_error:
                out["message"] = self.last_error
            if self.topo is not None and self.state == RunState.RUNNING:
                out.update(self.topo.status())
            return out

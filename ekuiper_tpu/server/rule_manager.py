"""Rule registry — analogue of eKuiper's RuleRegistry
(internal/server/rule_manager.go:112-238): owns the live RuleState machines,
coordinates create/start/stop/restart/delete, recovers rules at boot.
"""
from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional

from ..planner.planner import RuleDef, explain as plan_explain, plan_rule
from ..runtime.rule import RuleState, RunState
from ..utils.infra import PlanError, logger
from .processors import RuleProcessor


class RuleRegistry:
    def __init__(self, store) -> None:
        self.store = store
        self.processor = RuleProcessor(store)
        self._rules: Dict[str, RuleState] = {}
        self._lock = threading.RLock()

    # ---------------------------------------------------------------- recovery
    def recover(self) -> None:
        """Start rules marked running at last shutdown (boot recovery,
        reference: server.go rule restore). Rules parked in the
        persisted admission queue are re-enqueued with the controller —
        they were promised a start 'when pressure clears', and the
        in-memory queue died with the process."""
        run_table = self.store.kv("rule_run_state")
        aq_table = self.store.kv("admission_queue")
        live = set(self.processor.list())
        for rule_id in live:
            try:
                rule = self.processor.get(rule_id)
                rs = RuleState(rule, self.store)
                with self._lock:
                    self._rules[rule_id] = rs
                queued, q_ok = aq_table.get_ok(rule_id)
                if q_ok and queued is not None:
                    from ..runtime import control

                    ctl = control.controller()
                    if ctl is not None and ctl.enqueue(rule_id, {
                            "reason": (queued or {}).get("reason", ""),
                            "price": (queued or {}).get("price", {})}):
                        continue  # retried at control ticks
                    # no controller to honor the promise: start it now
                    # rather than strand it as pseudo-stopped
                    aq_table.delete(rule_id)
                    rs.start()
                    run_table.set(rule_id, True)
                    continue
                started, _ = run_table.get_ok(rule_id)
                auto_start = rule.options.get("triggered", True)
                if started if started is not None else auto_start:
                    # rebuild the admission ledger: the committed fold
                    # budget died with the process, and enforcing it
                    # against zero would over-admit a full engine. No
                    # gating here — boot recovery never refuses a rule
                    # that was already admitted.
                    self._bill(rule)
                    rs.start()
            except Exception as exc:
                logger.error("recover rule %s failed: %s", rule_id, exc)
        # queue entries for rules whose definition vanished are stale
        try:
            for rule_id in list(aq_table.keys()):
                if rule_id not in live:
                    aq_table.delete(rule_id)
        except Exception:
            pass

    # -------------------------------------------------------------------- CRUD
    def create(self, rule_json: Dict[str, Any]) -> str:
        rule = self.processor.create(rule_json)
        # validate by planning + constructing the FSM once (schedule options
        # are parsed there); any failure rolls the definition back so a
        # corrected re-POST with the same id works
        try:
            plan_rule(rule, self.store).close()
            rs = RuleState(rule, self.store)
        except Exception:
            self.processor.drop(rule.id)
            # the failed validation plan may have declared sharing
            # candidacy for a rule that will never exist
            from ..planner import sharing

            sharing.undeclare(rule.id)
            raise
        # admission control (runtime/control.py): price the rule against
        # the sharing cost model + live HBM/compile telemetry BEFORE it
        # starts. reject rolls the definition back with a STRUCTURED
        # decision; queue keeps the definition but defers the start to
        # the controller's next clear tick.
        from ..runtime import control

        triggered = rule.options.get("triggered", True)
        decision = {"decision": "accept"}
        if triggered:
            decision = control.admit_rule(rule, self.store)
        if decision["decision"] == "reject":
            self.processor.drop(rule.id)
            from ..planner import sharing

            sharing.undeclare(rule.id)
            raise control.AdmissionRejected(decision)
        with self._lock:
            self._rules[rule.id] = rs
        if decision["decision"] == "queue":
            ctl = control.controller()
            if ctl is not None and ctl.enqueue(rule.id, decision):
                self.store.kv("rule_run_state").set(rule.id, False)
                # persist the queue slot: a restart before pressure
                # clears must re-enqueue this rule (recover()), not
                # strand it indistinguishable from a user-stopped one
                self.store.kv("admission_queue").set(rule.id, {
                    "reason": decision.get("reason", ""),
                    "price": decision.get("price", {}),
                })
                return rule.id
            # no controller to retry it (or queue full): a queued rule
            # nobody will ever start is a silent reject — refuse loudly,
            # and COUNT it as the reject it became (enqueue never
            # counted a queue for it)
            if ctl is not None:
                ctl.note_admission("reject")
                from ..runtime.events import recorder

                recorder().record(
                    "admission", rule=rule.id, severity="warn",
                    decision="reject",
                    reason="admission queue unavailable")
            self.processor.drop(rule.id)
            from ..planner import sharing

            sharing.undeclare(rule.id)
            with self._lock:
                self._rules.pop(rule.id, None)
            raise control.AdmissionRejected({
                **decision, "decision": "reject",
                "reason": decision.get("reason", "")
                + " (admission queue unavailable)"})
        if triggered:
            ctl = control.controller()
            if ctl is not None:
                price = decision.get("price") or {}
                ctl.commit(rule.id,
                           float(price.get("fold_us_per_s", 0.0)),
                           placement=price.get("placement"))
            rs.start()
            self.store.kv("rule_run_state").set(rule.id, True)
        return rule.id

    def update(self, rule_json: Dict[str, Any]) -> None:
        # re-price the NEW definition before applying it: an update can
        # turn a cheap rule into one that blows the budgets. Updates are
        # never queued (allow_queue=False — the old definition keeps
        # running, there is nothing to defer) and the ledger is only
        # re-billed AFTER the processor accepts the new definition: a
        # parse-rejected update must not leave the ledger billing a
        # definition that never applied.
        from ..runtime import control

        candidate = RuleDef.from_dict(rule_json)
        decision = None
        if candidate.id:
            decision = control.admit_rule(candidate, self.store,
                                          allow_queue=False)
            if decision["decision"] == "reject":
                raise control.AdmissionRejected(decision)
        rule = self.processor.update(rule_json)
        # drop stale sharing candidacy (the SQL/options may have changed
        # its store key); the restart below re-declares under the new one
        from ..planner import sharing

        sharing.undeclare(rule.id)
        with self._lock:
            rs = self._rules.get(rule.id)
        if rs is not None:
            # cron rules waiting between firings are ACTIVE — an update must
            # re-arm their schedule, not silently deactivate it
            was_running = rs.state in (
                RunState.RUNNING, RunState.STARTING, RunState.SCHEDULED)
            rs.stop()
            # stop is ASYNC (FSM action queue): the old topo must release
            # its shared-source attachment before the new RuleState plans,
            # or the new start races "already attached" and dies
            # stopped_by_error — under rule-churn storms this silently
            # killed updated rules
            import time as _time

            deadline = _time.monotonic() + 10.0
            while _time.monotonic() < deadline and rs.state not in (
                    RunState.STOPPED, RunState.STOPPED_BY_ERR):
                _time.sleep(0.005)
            new_rs = RuleState(rule, self.store)
            with self._lock:
                self._rules[rule.id] = new_rs
            if was_running:
                # only a definition that will actually RUN is billed —
                # updating a stopped rule must not consume fold budget
                if decision is not None:
                    ctl = control.controller()
                    if ctl is not None:
                        price = decision.get("price") or {}
                        ctl.commit(
                            rule.id,
                            float(price.get("fold_us_per_s", 0.0)),
                            placement=price.get("placement"))
                new_rs.start()
        else:
            with self._lock:
                self._rules[rule.id] = RuleState(rule, self.store)

    def delete(self, rule_id: str) -> None:
        with self._lock:
            rs = self._rules.pop(rule_id, None)
        if rs is not None:
            rs.stop()
        self.processor.drop(rule_id)
        self.store.kv("rule_run_state").delete(rule_id)
        self.store.kv("admission_queue").delete(rule_id)
        # a deleted rule must stop counting as a sharing peer (ghost
        # declarations would make a later lone rule share with nobody)
        from ..planner import sharing

        sharing.undeclare(rule_id)
        # ...and must release its admission ledger entry / queue slot
        from ..runtime import control

        ctl = control.controller()
        if ctl is not None:
            ctl.release(rule_id)

    # --------------------------------------------------------------- lifecycle
    def _bill(self, rule) -> None:
        """Record a rule's priced fold cost in the admission ledger
        (no gating). The ledger tracks RUNNING rules: create-triggered,
        operator start, queue drain, and boot recovery all bill;
        stop/delete release."""
        from ..runtime import control

        ctl = control.controller()
        if ctl is None:
            return
        try:
            price = control.price_rule(rule, self.store)
            # price_rule never sets "placement" (the admission gate
            # does) — recovery/operator-start billing derives one from
            # the live ledger so restarts keep the per-chip accounting
            ctl.commit(rule.id, float(price.get("fold_us_per_s", 0.0)),
                       placement=control.bill_placement(price))
        except Exception:
            pass

    def _get(self, rule_id: str) -> RuleState:
        with self._lock:
            rs = self._rules.get(rule_id)
        if rs is None:
            # definition may exist without a live state (post-restart)
            rule = self.processor.get(rule_id)
            rs = RuleState(rule, self.store)
            with self._lock:
                self._rules[rule_id] = rs
        return rs

    def start(self, rule_id: str) -> None:
        # an operator start overrides a pending admission queue slot —
        # claim() pops it and commits its price atomically so the
        # controller won't start it a second time later
        from ..runtime import control

        ctl = control.controller()
        if ctl is not None and ctl.claim(rule_id) is None:
            # not queued (e.g. created triggered=false, or stopped then
            # restarted): the ledger must still bill what now runs
            self._bill(self._get(rule_id).rule)
        self.store.kv("admission_queue").delete(rule_id)
        self._get(rule_id).start()
        self.store.kv("rule_run_state").set(rule_id, True)

    def stop(self, rule_id: str) -> None:
        self._get(rule_id).stop()
        self.store.kv("rule_run_state").set(rule_id, False)
        # a stopped rule costs nothing: release its ledger entry (and
        # any pending queue slot — an operator stop cancels the promise
        # to start it later)
        from ..runtime import control

        ctl = control.controller()
        if ctl is not None:
            ctl.release(rule_id)
        self.store.kv("admission_queue").delete(rule_id)

    def restart(self, rule_id: str) -> None:
        self._get(rule_id).restart()
        self.store.kv("rule_run_state").set(rule_id, True)

    # ------------------------------------------------------------------ query
    def list(self, tags: Optional[List[str]] = None) -> List[Dict[str, Any]]:
        out = []
        for rule_id in self.processor.list():
            with self._lock:
                rs = self._rules.get(rule_id)
            raw, ok = self.processor._table().get_ok(rule_id)
            rule_tags = list(raw.get("tags") or []) if ok and isinstance(
                raw, dict) else []
            if tags and not set(tags) <= set(rule_tags):
                continue  # reference: tag filter requires ALL given tags
            status = rs.state.value if rs is not None else "stopped"
            entry = {"id": rule_id, "status": status}
            if rule_tags:
                entry["tags"] = rule_tags
            out.append(entry)
        return out

    def set_tags(self, rule_id: str, tags: List[str], add: bool) -> None:
        rule = self.processor.get(rule_id)
        if add:
            rule.tags = sorted(set(rule.tags) | set(tags))
        else:
            rule.tags = [t for t in rule.tags if t not in set(tags)]
        self.processor.update(rule.to_dict())

    def state(self, rule_id: str) -> Optional[RuleState]:
        """Live RuleState (None when not instantiated) — observability."""
        with self._lock:
            return self._rules.get(rule_id)

    def status(self, rule_id: str) -> Dict[str, Any]:
        return self._get(rule_id).status()

    def cpu_usage(self) -> Dict[str, Any]:
        """Per-rule cumulative busy time in ms (reference REST
        /rules/usage/cpu, rest.go:199 — there a sampling CPU profiler;
        here each node's accumulated in-process time, a documented
        wall-clock proxy)."""
        out: Dict[str, Any] = {}
        with self._lock:
            rules = dict(self._rules)
        for rule_id, rs in rules.items():
            topo = rs.topo  # capture: stop/restart may null it concurrently
            if topo is None:
                continue
            raw_us = {n.name: n.stats.process_time_us_total
                      for n in topo.all_nodes()}
            out[rule_id] = {
                "total_ms": round(sum(raw_us.values()) / 1000.0, 1),
                "nodes": {k: round(v / 1000.0, 1)
                          for k, v in raw_us.items()},
            }
        return out

    def latency_usage(self) -> Dict[str, Any]:
        """Per-rule ingest→emit latency summary (REST
        /rules/usage/latency, sibling of /rules/usage/cpu): the SLO view
        across every live rule at a glance — {count, p50, p90, p99, max}
        in ms off each topo's end-to-end histogram."""
        out: Dict[str, Any] = {}
        with self._lock:
            rules = dict(self._rules)
        for rule_id, rs in rules.items():
            topo = rs.topo  # capture: stop/restart may null it concurrently
            if topo is None:
                continue
            out[rule_id] = topo.e2e_hist.snapshot()
        return out

    def explain(self, rule_id: str) -> Dict[str, Any]:
        rule = self.processor.get(rule_id)
        out = plan_explain(rule, self.store)
        # a running rule's order-statistic buffers report their live rung
        # (the plan gives the first one)
        rs = self.state(rule_id)
        topo = rs.topo if rs is not None else None
        bufs = (out.get("order_stats") or {}).get("buffers")
        if bufs and topo is not None:
            for node in topo.ops:
                if getattr(node, "_orderstat", False):
                    for buf in bufs:
                        buf["rung"] = node.orderstat_buffer_rows
        return out

    def topo_json(self, rule_id: str) -> Dict[str, Any]:
        rs = self._get(rule_id)
        if rs.topo is not None:
            return rs.topo.topo_json()
        topo = plan_rule(rs.rule, self.store)
        out = topo.topo_json()
        topo.close()
        return out

    def validate(self, rule_json: Dict[str, Any]) -> Dict[str, Any]:
        rule = RuleDef.from_dict(rule_json)
        if not rule.sql and rule.graph is None:
            return {"valid": False, "error": "rule sql or graph is required"}
        # a validation probe must neither LEAVE sharing candidacy behind
        # (a phantom peer would flip later lone rules to shared) nor
        # OVERWRITE a registered rule's live declaration (probing an
        # existing id with a different window would skew the pane GCD of
        # future stores); the rollback is scoped to the probed id so
        # concurrent rule CRUD on other rules is untouched
        from ..planner import sharing

        existed = rule.id in set(self.processor.list()) if rule.id else False
        try:
            with sharing.probe_declarations(rule.id):
                try:
                    plan_rule(rule, self.store).close()
                    return {"valid": True}
                except Exception as exc:
                    return {"valid": False, "error": str(exc)}
        finally:
            # probe restore races a concurrent DELETE of the same id: the
            # restored declaration would resurrect a ghost peer — drop it
            # when the rule vanished (or never existed) during the probe
            if rule.id and (not existed
                            or rule.id not in set(self.processor.list())):
                sharing.undeclare(rule.id)

    def reset_state(self, rule_id: str) -> None:
        """Drop checkpointed state (REST /rules/{id}/reset_state)."""
        self.store.drop(f"checkpoint:{rule_id}")

    def stop_all(self) -> None:
        with self._lock:
            rules = list(self._rules.values())
        for rs in rules:
            rs.stop()

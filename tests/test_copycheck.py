"""copycheck rule tests (copycat_tpu/analysis/ — docs/ANALYSIS.md).

Every rule gets a seeded-violation positive AND a clean negative, so a
rule that silently stops firing fails here before CI's `--strict` gate
goes blind. Engine behavior (suppressions, baseline, cache, exit codes)
is tested over a temp repo so the real tree's baseline never leaks in.
"""

import ast
import json
import os
import subprocess
import sys
import textwrap

import pytest

from copycat_tpu.analysis import ALL_RULES
from copycat_tpu.analysis.engine import (
    LintContext,
    discover,
    lint_file,
    run_lint,
    update_wire_golden,
)
from copycat_tpu.analysis.findings import (
    Baseline,
    Finding,
    is_suppressed,
    scan_suppressions,
)
from copycat_tpu.analysis.rules_asyncio import (
    check_loop_blocking,
    check_orphan_task,
)
from copycat_tpu.analysis.callgraph import CallGraph
from copycat_tpu.analysis.rules_await_tear import check_await_tear
from copycat_tpu.analysis.rules_contracts import (
    check_durability_order,
    check_exit_contract,
    check_span_contract,
    parse_exit_codes,
    parse_span_catalog,
)
from copycat_tpu.analysis.rules_jit import check_jit_purity, collect_jit_roots
from copycat_tpu.analysis.rules_registries import (
    check_knob_registry,
    check_metric_registry,
    parse_knob_registry,
    parse_metric_catalog,
)
from copycat_tpu.analysis.rules_wire import check_wire_schema, render_golden

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def live_lint():
    """The whole-tree lint, once a module (6-7 s a walk): nine tests read
    it, each for its own rule."""
    return run_lint(root=REPO, use_cache=False)


def _tree(code: str) -> ast.Module:
    return ast.parse(textwrap.dedent(code))


# ---------------------------------------------------------------------------
# loop-blocking
# ---------------------------------------------------------------------------


def test_loop_blocking_flags_sleep_fsync_open_and_device_fetch():
    tree = _tree("""
        import time, os, jax

        async def bad(f):
            time.sleep(1)
            os.fsync(3)
            open("/tmp/x")
            jax.device_get(f)
            f.block_until_ready()
    """)
    rules = [f.message for f in check_loop_blocking(tree, "pkg/mod.py")]
    assert len(rules) == 5
    assert any("time.sleep" in m for m in rules)
    assert any("os.fsync" in m for m in rules)
    assert any("open" in m for m in rules)
    assert any("device_get" in m for m in rules)
    assert any("block_until_ready" in m for m in rules)


def test_loop_blocking_ignores_sync_defs_and_nested_sync_defs():
    tree = _tree("""
        import time

        def fine():
            time.sleep(1)

        async def outer():
            def helper():
                time.sleep(1)  # judged at helper's call site
            return helper
    """)
    assert check_loop_blocking(tree, "pkg/mod.py") == []


def test_loop_blocking_allows_asyncio_sleep():
    tree = _tree("""
        import asyncio

        async def fine():
            await asyncio.sleep(0.1)
    """)
    assert check_loop_blocking(tree, "pkg/mod.py") == []


def _graph(path: str, code: str) -> tuple[ast.Module, CallGraph]:
    tree = _tree(code)
    return tree, CallGraph.build({path: tree})


def test_loop_blocking_interprocedural_reaches_into_sync_helpers():
    # the v2 tentpole: the blocking call sits in a SYNC helper — lexically
    # invisible to the v1 rule — and is flagged because the call graph
    # proves the helper reachable from an async def
    tree, graph = _graph("pkg/mod.py", """
        import subprocess

        def run_tool(cmd):
            return subprocess.run(cmd)

        async def pump(cmd):
            return run_tool(cmd)
    """)
    assert check_loop_blocking(tree, "pkg/mod.py") == []  # lexical-only: blind
    found = check_loop_blocking(tree, "pkg/mod.py", graph)
    assert len(found) == 1
    assert found[0].symbol == "run_tool"
    assert "reachable from an async def" in found[0].message
    assert found[0].via == ["pkg/mod.py::pump", "pkg/mod.py::run_tool"]
    # ...and the chain closes transitively through sync middlemen
    tree2, graph2 = _graph("pkg/mod.py", """
        import subprocess

        def inner(cmd):
            return subprocess.run(cmd)

        def outer(cmd):
            return inner(cmd)

        async def pump(cmd):
            return outer(cmd)
    """)
    found = check_loop_blocking(tree2, "pkg/mod.py", graph2)
    assert len(found) == 1 and found[0].symbol == "inner"
    assert found[0].via[-1] == "pkg/mod.py::inner"


def test_loop_blocking_spares_helpers_no_async_def_reaches():
    tree, graph = _graph("pkg/mod.py", """
        import subprocess

        def run_tool(cmd):
            return subprocess.run(cmd)

        def sync_caller(cmd):
            return run_tool(cmd)
    """)
    assert check_loop_blocking(tree, "pkg/mod.py", graph) == []


def test_loop_blocking_deploy_plane_blocklist_entries():
    # the post-PR 7 hazards: child-process waits, blocking connects,
    # sync stream copies (the deploy plane's bread and butter)
    tree = _tree("""
        import os, socket, shutil, subprocess

        async def bad(a, b, proc):
            os.waitpid(1, 0)
            socket.create_connection(("host", 1))
            shutil.copyfileobj(a, b)
            subprocess.check_output(["x"])
            proc.wait()
    """)
    found = check_loop_blocking(tree, "pkg/mod.py")
    assert len(found) == 5


def test_loop_blocking_awaited_wait_is_the_asyncio_form():
    # `proc.wait()` blocks (Popen.wait); `await proc.wait()` is the
    # asyncio.subprocess coroutine — only the bare call is a finding
    tree = _tree("""
        import asyncio

        async def fine(proc, cond):
            await proc.wait()
            await asyncio.wait_for(cond.wait(), 1.0)

        async def bad(proc):
            proc.wait()
    """)
    found = check_loop_blocking(tree, "pkg/mod.py")
    assert len(found) == 1 and found[0].symbol == "bad"


def test_loop_blocking_live_tree_is_clean(live_lint):
    assert [f for f in live_lint.findings if f.rule == "loop-blocking"] == []


# ---------------------------------------------------------------------------
# orphan-task
# ---------------------------------------------------------------------------


def test_orphan_task_flags_raw_spawns():
    tree = _tree("""
        import asyncio

        async def bad(loop, coro):
            loop.create_task(coro)
            asyncio.ensure_future(coro)
            asyncio.create_task(coro)
    """)
    found = check_orphan_task(tree, "pkg/mod.py")
    assert len(found) == 3
    assert all(f.rule == "orphan-task" for f in found)


def test_orphan_task_exempts_tasks_module_and_spawn_calls():
    tree = _tree("""
        from copycat_tpu.utils.tasks import spawn

        async def fine(coro):
            spawn(coro, name="x")
    """)
    assert check_orphan_task(tree, "pkg/mod.py") == []
    raw = _tree("async def f(loop, c):\n    loop.create_task(c)\n")
    assert check_orphan_task(raw, "copycat_tpu/utils/tasks.py") == []


def test_live_tree_has_no_raw_spawns(live_lint):
    # the satellite fix: every create_task/ensure_future routed through
    # utils/tasks.spawn — keep it that way
    assert [f for f in live_lint.findings if f.rule == "orphan-task"] == []


# ---------------------------------------------------------------------------
# await-tear
# ---------------------------------------------------------------------------

TEAR = """
    class RaftServer:
        async def transition(self, peer):
            term = self.term
            response = await self.send(peer, term)
            self.term = response.term
"""

GUARDED = """
    class RaftServer:
        async def transition(self, peer):
            term = self.term
            response = await self.send(peer, term)
            if self.term != term:
                return
            self.term = response.term
"""


def test_await_tear_flags_unguarded_write_after_await():
    found = check_await_tear(_tree(TEAR), "server/raft.py")
    assert len(found) == 1
    assert found[0].rule == "await-tear"
    assert "self.term" in found[0].message
    assert found[0].symbol == "RaftServer.transition"


def test_await_tear_accepts_epoch_guard():
    assert check_await_tear(_tree(GUARDED), "server/raft.py") == []


# The multi-raft refactor moved protected fields from ``self`` onto the
# group-state object (server/raft_group.py; server code reaches them
# through aliases like ``grp``): the rule keys events by (base, field),
# so a torn write through an alias still fires, a guard on the SAME base
# discharges it, and a guard on a DIFFERENT base does not.
GROUP_TEAR = """
    class RaftServer:
        async def transition(self, peer):
            grp = self.groups[0]
            term = grp.term
            response = await self.send(peer, term)
            grp.term = response.term
"""

GROUP_GUARDED = """
    class RaftServer:
        async def transition(self, peer):
            grp = self.groups[0]
            term = grp.term
            response = await self.send(peer, term)
            if grp.term != term:
                return
            grp.term = response.term
"""

GROUP_CROSS_BASE_GUARD = """
    class RaftServer:
        async def transition(self, peer, other):
            grp = self.groups[0]
            term = grp.term
            response = await self.send(peer, term)
            if other.term != term:
                return
            grp.term = response.term
"""


def test_await_tear_flags_group_state_write_after_await():
    found = check_await_tear(_tree(GROUP_TEAR), "server/raft_group.py")
    assert len(found) == 1
    assert "grp.term" in found[0].message


def test_await_tear_accepts_group_state_epoch_guard():
    assert check_await_tear(_tree(GROUP_GUARDED),
                            "server/raft_group.py") == []


def test_await_tear_guard_must_reread_the_same_base():
    found = check_await_tear(_tree(GROUP_CROSS_BASE_GUARD),
                             "server/raft_group.py")
    assert len(found) == 1
    assert "grp.term" in found[0].message


def test_await_tear_scope_covers_raft_group_file():
    # basename scope: the refactored per-group core is checked, other
    # modules are not
    assert check_await_tear(_tree(GROUP_TEAR), "server/raft_group.py")
    assert check_await_tear(_tree(GROUP_TEAR), "client/client.py") == []


def test_await_tear_accepts_role_guard_and_flags_log_tail():
    role_guard = _tree("""
        class RaftServer:
            async def ok(self):
                index = self.commit_index
                await self.quorum()
                if self.role != "leader":
                    return
                self.commit_index = index + 1
    """)
    assert check_await_tear(role_guard, "server/raft.py") == []
    log_tear = _tree("""
        class RaftServer:
            async def bad(self, entries):
                last = self.log.last_index
                await self.quorum()
                self.log.truncate(last)
    """)
    found = check_await_tear(log_tear, "server/raft.py")
    assert len(found) == 1 and "self.log" in found[0].message


def test_await_tear_ignores_pre_await_writes_and_other_files():
    pre = _tree("""
        class RaftServer:
            async def ok(self):
                self.term += 1
                await self.persist()
    """)
    assert check_await_tear(pre, "server/raft.py") == []
    # rule is scoped to raft modules
    assert check_await_tear(_tree(TEAR), "client/client.py") == []


def test_await_tear_live_tree_is_clean(live_lint):
    assert [f for f in live_lint.findings if f.rule == "await-tear"] == []


# --- interprocedural (copycheck v2): the call graph closes the two
# lexical blind spots — writes hidden in called helpers, and suspension
# classification in both directions -----------------------------------------

HIDDEN_WRITE = """
    class RaftGroup:
        def _commit_term(self, t):
            self.term = t

        async def transition(self, peer):
            term = self.term
            response = await self.send(peer, term)
            self._commit_term(response.term)
"""


def test_await_tear_interprocedural_flags_write_hidden_in_helper():
    # the fixture the lexical rule PROVABLY missed: no attribute store
    # is lexically visible after the await — the torn write hides inside
    # the called helper, surfaced by the effect summary
    tree = _tree(HIDDEN_WRITE)
    assert check_await_tear(tree, "server/raft_group.py") == []  # v1 view
    graph = CallGraph.build({"server/raft_group.py": tree})
    found = check_await_tear(tree, "server/raft_group.py", graph)
    assert len(found) == 1
    assert "write hidden in" in found[0].message
    assert "self.term" in found[0].message
    assert found[0].via == ["server/raft_group.py::RaftGroup._commit_term"]


def test_await_tear_interprocedural_guard_still_discharges_hidden_write():
    tree = _tree("""
        class RaftGroup:
            def _commit_term(self, t):
                self.term = t

            async def transition(self, peer):
                term = self.term
                response = await self.send(peer, term)
                if self.term != term:
                    return
                self._commit_term(response.term)
    """)
    graph = CallGraph.build({"server/raft_group.py": tree})
    assert check_await_tear(tree, "server/raft_group.py", graph) == []


def test_await_tear_never_suspending_await_is_not_an_interleaving_point():
    # precision the lexical rule lacked the OTHER way: an await of a
    # local coroutine with no yield point of its own cannot interleave
    tree = _tree("""
        class RaftGroup:
            async def _bump(self, x):
                return x + 1

            async def transition(self):
                term = self.term
                term = await self._bump(term)
                self.term = term
    """)
    assert len(check_await_tear(tree, "server/raft.py")) == 1  # v1: flagged
    graph = CallGraph.build({"server/raft.py": tree})
    assert check_await_tear(tree, "server/raft.py", graph) == []


def test_await_tear_async_with_is_a_suspension_point():
    # `async with` acquires on entry — a yield point with no Await node,
    # invisible to the lexical rule
    tree = _tree("""
        class RaftGroup:
            async def transition(self):
                term = self.term
                async with self.gate:
                    self.term = term + 1
    """)
    graph = CallGraph.build({"server/raft.py": tree})
    found = check_await_tear(tree, "server/raft.py", graph)
    assert len(found) == 1 and "self.term" in found[0].message


def test_await_tear_summary_cache_never_keeps_truncated_entries():
    # regression: summarizing `_a` walks _b/_c/_d at depths 1-3 and the
    # depth cap truncates `_w`'s write out of `_d`'s summary — that
    # truncated view must NOT be cached, or the later direct
    # `self._d()` call site (a fresh depth-0 query) misses a real tear
    tree = _tree("""
        class RaftGroup:
            def _w(self):
                self.term = 0

            def _d(self):
                self._w()

            def _c(self):
                self._d()

            def _b(self):
                self._c()

            def _a(self):
                self._b()

            async def deep(self, peer):
                t = self.term
                await self.send(peer)
                self._a()

            async def shallow(self, peer):
                t = self.term
                await self.send(peer)
                self._d()
    """)
    graph = CallGraph.build({"server/raft_group.py": tree})
    found = check_await_tear(tree, "server/raft_group.py", graph)
    assert [f.symbol for f in found] == ["RaftGroup.shallow"]


def test_callgraph_ambiguous_module_basename_stays_conservative():
    # two homonymous modules both define `load`: resolution must refuse
    # to guess (a wrong never-suspending guess would un-flag a real
    # interleaving point) — the await stays a suspension and the tear
    # fires; with the ambiguity removed, the never-suspending resolution
    # discharges it
    raft = _tree("""
        from copycat_tpu.client import state

        class RaftGroup:
            async def t(self):
                term = self.term
                await state.load()
                self.term = term + 1
    """)
    pure_state = _tree("async def load():\n    return 1\n")
    trees = {"server/raft.py": raft,
             "client/state.py": pure_state,
             "server/state.py": _tree("async def load():\n    return 2\n")}
    graph = CallGraph.build(trees)
    assert len(check_await_tear(raft, "server/raft.py", graph)) == 1
    unique = CallGraph.build({"server/raft.py": raft,
                              "client/state.py": pure_state})
    assert check_await_tear(raft, "server/raft.py", unique) == []


def test_loop_blocking_skips_nested_defs_inside_reachable_sync_helpers():
    # a nested def inside a sync helper is a callback, not inline code:
    # reachability must not descend into it (same rule as nested defs
    # inside async defs — judged where something calls it)
    tree, graph = _graph("pkg/mod.py", """
        import shutil

        def helper(tmp, bus):
            def on_done():
                shutil.rmtree(tmp)
            bus.subscribe(on_done)

        async def pump(tmp, bus):
            helper(tmp, bus)
    """)
    assert check_loop_blocking(tree, "pkg/mod.py", graph) == []


def test_await_tear_scope_covers_the_deploy_plane():
    # the compartmentalized tiers run the same ordering contracts in
    # their own processes — in scope since v2
    assert check_await_tear(_tree(TEAR), "copycat_tpu/deploy/ingress.py")
    assert check_await_tear(_tree(TEAR), "copycat_tpu/deploy/supervisor.py")
    assert check_await_tear(_tree(TEAR), "copycat_tpu/deploy/topology.py") == []


# ---------------------------------------------------------------------------
# durability-order
# ---------------------------------------------------------------------------

RESOLVE_BEFORE_SYNC = """
    class RaftGroup:
        def on_quorum(self, index, result):
            fut = self._commit_futures.pop(index, None)
            if fut is not None and not fut.done():
                fut.set_result((index, result, None))
            self._sync_log()
"""

RESOLVE_AFTER_SYNC = """
    class RaftGroup:
        def on_quorum(self, index, result):
            self._sync_log()
            fut = self._commit_futures.pop(index, None)
            if fut is not None and not fut.done():
                fut.set_result((index, result, None))
"""


def test_durability_order_flags_resolve_before_sync():
    # the seeded fixture from the issue: the future resolves BEFORE the
    # commit-boundary fsync — an acknowledged write a power loss erases
    found = check_durability_order(_tree(RESOLVE_BEFORE_SYNC),
                                   "server/raft_group.py")
    assert len(found) == 1
    assert found[0].rule == "durability-order"
    assert "fut" in found[0].message
    assert found[0].symbol == "RaftGroup.on_quorum"


def test_durability_order_accepts_resolve_dominated_by_sync():
    assert check_durability_order(_tree(RESOLVE_AFTER_SYNC),
                                  "server/raft_group.py") == []


def test_durability_order_dominance_closes_through_class_callers():
    # the ack lives in a helper with no sync of its own: discharged
    # because every same-class caller reaches it past a commit-boundary
    # sync — and NOT discharged once the helper is also entered from
    # outside the class (the fused-dispatch seam)
    src = """
        class RaftGroup:
            def advance(self, index, result):
                self._sync_log()
                self._resolve(index, result)

            def _resolve(self, index, result):
                fut = self._commit_futures.pop(index, None)
                fut.set_result((index, result, None))
    """
    assert check_durability_order(_tree(src), "server/raft_group.py") == []
    found = check_durability_order(_tree(src), "server/raft_group.py",
                                   external_attr_calls={"_resolve"})
    assert len(found) == 1 and found[0].symbol == "RaftGroup._resolve"


def test_durability_order_exempts_error_resolves_and_other_classes():
    # a payload naming an msg.ERROR_CODE constant reports failure — it
    # acknowledges nothing; and the rule is scoped to RaftGroup
    err = _tree("""
        class RaftGroup:
            def reject(self, index):
                fut = self._commit_futures.pop(index, None)
                if fut is not None:
                    fut.set_result((index, None, msg.NO_LEADER))
    """)
    assert check_durability_order(err, "server/raft_group.py") == []
    other = _tree(RESOLVE_BEFORE_SYNC.replace("RaftGroup", "ReadIndexPlane"))
    assert check_durability_order(other, "server/raft_group.py") == []
    assert check_durability_order(_tree(RESOLVE_BEFORE_SYNC),
                                  "client/client.py") == []


def test_durability_order_flags_undominated_success_append_ack():
    tree = _tree("""
        class RaftGroup:
            def on_append(self, request):
                self.log.append_replicated_block(request.entries)
                return AppendResponse(term=self.term, success=True)
    """)
    found = check_durability_order(tree, "server/raft_group.py")
    assert len(found) == 1 and "success append ack" in found[0].message
    synced = _tree("""
        class RaftGroup:
            def on_append(self, request):
                self.log.append_replicated_block(request.entries)
                self._sync_log()
                return AppendResponse(term=self.term, success=True)
    """)
    assert check_durability_order(synced, "server/raft_group.py") == []


def test_durability_order_live_tree_carries_only_justified_baselines(live_lint):
    assert [f for f in live_lint.findings if f.rule == "durability-order"] == []
    # the fused-dispatch seam findings ride the baseline, each with a
    # written dominance argument (no TODO placeholders — CI's contract)
    carried = [f for f in live_lint.baselined if f.rule == "durability-order"]
    assert carried, "the fused-seam findings should be baselined, not gone"
    baseline = json.load(open(os.path.join(REPO, ".copycheck-baseline.json")))
    for entry in baseline["findings"]:
        assert entry["justification"].strip(), entry
        assert "TODO" not in entry["justification"], entry


# ---------------------------------------------------------------------------
# span-pairing
# ---------------------------------------------------------------------------

SPAN_VOCAB_MD = """
### Span-name vocabulary

| name | phase |
|---|---|
| `quorum.wait` | commit |
| `group.fsync` | commit |
"""


def test_span_pairing_validates_names_against_the_vocabulary():
    catalog = parse_span_catalog(SPAN_VOCAB_MD)
    assert catalog == {"quorum.wait", "group.fsync"}
    tree = _tree("""
        class G:
            def ok(self, trace, t0, t1):
                self._trace_span(trace, "quorum.wait", t0, t1)

            def bad(self, trace, t0, t1):
                self._trace_span(trace, "quorum.wiat", t0, t1)
    """)
    found = check_span_contract(tree, "copycat_tpu/server/raft_group.py",
                                catalog)
    assert len(found) == 1
    assert "quorum.wiat" in found[0].message
    assert found[0].symbol == "G.bad"


def test_span_pairing_forwarding_wrappers_and_dynamic_names():
    catalog = {"quorum.wait"}
    # the name is a parameter of the enclosing function: a forwarding
    # wrapper — its CALLERS are checked instead
    wrapper = _tree("""
        class G:
            def _trace_span(self, trace, name, start, end):
                self.tracer.span(trace, name, start, end)
    """)
    assert check_span_contract(wrapper, "copycat_tpu/server/raft.py",
                               catalog) == []
    # any other dynamic name is a finding (it dodges the vocabulary)
    dynamic = _tree("""
        class G:
            def record(self, trace, t0, t1):
                self.tracer.span(trace, self.pick_name(), t0, t1)
    """)
    found = check_span_contract(dynamic, "copycat_tpu/server/raft.py",
                                catalog)
    assert len(found) == 1 and "dynamic span name" in found[0].message


def test_span_pairing_flags_with_over_span_and_bare_timer():
    tree = _tree("""
        class G:
            def timed(self, trace, metrics, t0, t1):
                with self.tracer.span(trace, "quorum.wait", t0, t1):
                    pass
                metrics.timer("commit_ms")
                with metrics.timer("commit_ms"):
                    pass
    """)
    found = check_span_contract(tree, "copycat_tpu/server/raft.py",
                                {"quorum.wait"})
    msgs = [f.message for f in found]
    assert len(found) == 2
    assert any("`with` over a span-record call" in m for m in msgs)
    assert any("opened and discarded" in m for m in msgs)


def test_span_pairing_flags_call_missing_timestamps():
    # the record family's signature is (trace, name, start, end, ...):
    # a 3-arg call has no end timestamp — nothing pairable is recorded
    tree = _tree("""
        class G:
            def bad(self, trace, t0):
                self._trace_span(trace, "quorum.wait", t0)
    """)
    found = check_span_contract(tree, "copycat_tpu/server/raft.py",
                                {"quorum.wait"})
    assert len(found) == 1 and "fewer than 4" in found[0].message


def test_durability_order_error_exemption_is_msg_scoped():
    # only msg.X constants mark an error resolve; an unrelated all-caps
    # constant in a SUCCESS payload must not dodge the dominance check
    tree = _tree("""
        class RaftGroup:
            def resolve(self, index):
                fut = self._commit_futures.pop(index, None)
                fut.set_result((index, cfg.MAX_INFLIGHT, None))
    """)
    found = check_durability_order(tree, "server/raft_group.py")
    assert len(found) == 1


def test_span_pairing_live_tree_names_all_in_vocabulary(live_lint):
    catalog = parse_span_catalog(
        open(os.path.join(REPO, "docs", "OBSERVABILITY.md")).read())
    assert catalog and "quorum.wait" in catalog
    assert [f for f in live_lint.findings if f.rule == "span-pairing"] == []


def test_span_pairing_admits_edge_spans_and_still_fires_uncataloged():
    """The edge read tier's span names (docs/EDGE_READS.md) are in the
    REAL vocabulary table, and the rule still fires on an uncataloged
    edge-adjacent name — the seeded-violation proof that adding rows
    did not blunt the gate."""
    catalog = parse_span_catalog(
        open(os.path.join(REPO, "docs", "OBSERVABILITY.md")).read())
    assert {"client.edge_serve", "client.delta"} <= catalog
    tree = _tree("""
        class EdgeReadTier:
            def ok(self, tracer, trace, t0, t1):
                tracer.span(trace, "client.edge_serve", t0, t1)
                tracer.span(trace, "client.delta", t0, t1)

            def bad(self, tracer, trace, t0, t1):
                tracer.span(trace, "client.edge_servee", t0, t1)
    """)
    found = check_span_contract(tree, "copycat_tpu/client/edge.py",
                                catalog)
    assert len(found) == 1
    assert "client.edge_servee" in found[0].message


# ---------------------------------------------------------------------------
# exit-code
# ---------------------------------------------------------------------------


def test_exit_code_contract_flags_undocumented_codes():
    codes = parse_exit_codes(
        open(os.path.join(REPO, "docs", "DEPLOYMENT.md")).read())
    assert codes == {0, 1, 2}
    tree = _tree("""
        import sys

        def main():
            if bad_config():
                sys.exit(2)
            if crashed():
                sys.exit(1)
            sys.exit(3)
    """)
    found = check_exit_contract(tree, "copycat_tpu/deploy/child.py", codes)
    assert len(found) == 1
    assert "exit code 3" in found[0].message
    # scope: only the deploy-plane mains are under the contract
    assert check_exit_contract(
        tree, "copycat_tpu/testing/verdict.py", codes) == []


def test_exit_code_contract_sees_negative_literals():
    # sys.exit(-1) is a UnaryOp, not a Constant — and 255 at the
    # process boundary, squarely in the crash-restart lane
    tree = _tree("""
        import sys

        def main():
            sys.exit(-1)
    """)
    found = check_exit_contract(tree, "copycat_tpu/deploy/child.py",
                                {0, 1, 2})
    assert len(found) == 1 and "exit code -1" in found[0].message
    # strings exit with code 1 (the documented crash code) — not flagged
    s = _tree("import sys\nsys.exit('bad config')\n")
    assert check_exit_contract(s, "copycat_tpu/deploy/child.py",
                               {0, 1, 2}) == []


def test_exit_code_contract_live_tree_is_clean(live_lint):
    assert [f for f in live_lint.findings if f.rule == "exit-code"] == []


# ---------------------------------------------------------------------------
# knob-registry
# ---------------------------------------------------------------------------

KNOBS_SRC = '_knob("COPYCAT_GOOD", "int", 1, "doc", section="bench")\n'


def test_knob_registry_flags_direct_reads_and_unregistered_names():
    registered = parse_knob_registry(KNOBS_SRC)
    assert registered == {"COPYCAT_GOOD"}
    tree = _tree("""
        import os
        from copycat_tpu.utils import knobs

        a = os.environ.get("COPYCAT_GOOD", "1")
        b = os.getenv("COPYCAT_GOOD")
        c = os.environ["COPYCAT_GOOD"]
        d = knobs.get_int("COPYCAT_MISSING")
    """)
    found = check_knob_registry(tree, "copycat_tpu/mod.py", registered)
    assert len(found) == 4
    assert sum("direct env read" in f.message for f in found) == 3
    assert sum("not registered" in f.message for f in found) == 1


def test_knob_registry_allows_writes_typed_getters_and_knobs_module():
    registered = {"COPYCAT_GOOD"}
    tree = _tree("""
        import os
        from copycat_tpu.utils import knobs

        os.environ["COPYCAT_GOOD"] = "0"     # staging env for a child
        v = knobs.get_int("COPYCAT_GOOD")
        w = os.environ.get("OTHER_PREFIX")   # not a knob
    """)
    assert check_knob_registry(tree, "copycat_tpu/mod.py", registered) == []
    raw = _tree('x = os.environ.get("COPYCAT_GOOD")')
    assert check_knob_registry(raw, "copycat_tpu/utils/knobs.py",
                               registered) == []


def test_live_tree_knob_reads_all_routed(live_lint):
    assert [f for f in live_lint.findings if f.rule == "knob-registry"] == []


# ---------------------------------------------------------------------------
# metric-registry
# ---------------------------------------------------------------------------

CATALOG_MD = """
## Metric name catalog

| name | kind | meaning |
|---|---|---|
| `good_metric` | counter | fine |
| `labeled{lane}` | counter | fine |
"""


def test_metric_registry_flags_unknown_names_bad_labels_and_dynamic():
    catalog = parse_metric_catalog(CATALOG_MD)
    assert catalog == {"good_metric": set(), "labeled": {"lane"}}
    tree = _tree("""
        m.counter("good_metric")
        m.counter("labeled", lane="fast")
        m.counter("unknown_metric")
        m.counter("labeled", wrong="x")
        m.counter(dynamic_name)
    """)
    found = check_metric_registry(tree, "copycat_tpu/mod.py", catalog)
    msgs = [f.message for f in found]
    assert len(found) == 3
    assert any("unknown_metric" in m for m in msgs)
    assert any("labels {wrong}" in m for m in msgs)
    assert any("dynamic metric name" in m for m in msgs)


def test_metric_registry_checks_both_branches_of_a_ternary():
    catalog = {"a_metric": set(), "b_metric": set()}
    ok = _tree('m.counter("a_metric" if cond else "b_metric")')
    assert check_metric_registry(ok, "copycat_tpu/mod.py", catalog) == []
    bad = _tree('m.counter("a_metric" if cond else "nope")')
    found = check_metric_registry(bad, "copycat_tpu/mod.py", catalog)
    assert len(found) == 1 and "nope" in found[0].message


def test_live_tree_metric_names_all_cataloged(live_lint):
    assert [f for f in live_lint.findings if f.rule == "metric-registry"] == []


def test_catalog_has_no_orphan_entries():
    """Bidirectional sync: every catalog entry is recorded somewhere in
    the tree (a deleted metric must leave the catalog too)."""
    catalog = parse_metric_catalog(
        open(os.path.join(REPO, "docs", "OBSERVABILITY.md")).read())
    used: set[str] = set()
    for rel in discover(REPO):
        if not rel.startswith("copycat_tpu/"):
            continue
        tree = ast.parse(open(os.path.join(REPO, rel)).read())
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("counter", "gauge", "histogram",
                                           "timer")
                    and node.args):
                for arg in ([node.args[0].body, node.args[0].orelse]
                            if isinstance(node.args[0], ast.IfExp)
                            else [node.args[0]]):
                    if isinstance(arg, ast.Constant) and isinstance(
                            arg.value, str):
                        used.add(arg.value)
    # dynamic loops register the documented device.* families
    from copycat_tpu.models.telemetry import _COUNTERS, _GAUGES
    used |= set(_COUNTERS) | set(_GAUGES)
    orphans = set(catalog) - used
    assert not orphans, f"catalog entries no code records: {sorted(orphans)}"


# ---------------------------------------------------------------------------
# wire-schema
# ---------------------------------------------------------------------------

WIRE_OK = """
    @serialize_with(200)
    class Ping(Message):
        _fields = ("a", "b")
"""


def test_wire_schema_detects_drift_reorder_and_duplicate_ids():
    golden = {"200": ["Ping", ["a", "b"]]}
    assert check_wire_schema(_tree(WIRE_OK),
                             "copycat_tpu/protocol/messages.py",
                             golden) == []
    reordered = _tree("""
        @serialize_with(200)
        class Ping(Message):
            _fields = ("b", "a")
    """)
    found = check_wire_schema(reordered,
                              "copycat_tpu/protocol/messages.py", golden)
    assert len(found) == 1 and "drifted" in found[0].message
    assert "--update-golden" in found[0].message
    dup = _tree("""
        @serialize_with(200)
        class Ping(Message):
            _fields = ("a",)

        @serialize_with(200)
        class Pong(Message):
            _fields = ("b",)
    """)
    found = check_wire_schema(dup, "copycat_tpu/protocol/messages.py",
                              golden)
    assert any("reused" in f.message for f in found)


def test_wire_schema_flags_new_and_removed_ids():
    golden = {"200": ["Ping", ["a", "b"]], "201": ["Pong", ["c"]]}
    found = check_wire_schema(_tree(WIRE_OK),
                              "copycat_tpu/protocol/messages.py", golden)
    assert len(found) == 1 and "disappeared" in found[0].message
    added = _tree(WIRE_OK + """
    @serialize_with(202)
    class New(Message):
        _fields = ("x",)
    """)
    found = check_wire_schema(added, "copycat_tpu/protocol/messages.py",
                              {"200": ["Ping", ["a", "b"]]})
    assert len(found) == 1 and "new" in found[0].message


def test_wire_golden_matches_live_messages():
    src = open(os.path.join(REPO, "copycat_tpu", "protocol",
                            "messages.py")).read()
    rendered = render_golden(ast.parse(src))
    committed = open(os.path.join(REPO, "tests", "golden",
                                  "wire_schema.json")).read()
    assert rendered == committed, (
        "protocol/messages.py schema drifted from tests/golden/"
        "wire_schema.json — if intentional, regenerate with "
        "`copycat-tpu lint --update-golden` and commit the diff")


# ---------------------------------------------------------------------------
# jit-purity
# ---------------------------------------------------------------------------


def test_jit_purity_flags_impurity_reachable_from_jitted_root():
    jitter = _tree("step_fn = jax.jit(partial(step, config=c))")
    roots = collect_jit_roots({"models/raft_groups.py": jitter})
    assert "step" in roots
    opsmod = _tree("""
        import time

        def helper(x):
            return time.time() + x

        def step(state):
            return helper(state)

        def unrelated():
            return time.time()
    """)
    found = check_jit_purity(opsmod, "copycat_tpu/ops/consensus.py", roots)
    assert len(found) == 1
    assert found[0].symbol == "helper"
    assert "time.time" in found[0].message


def test_jit_purity_allows_jax_random_and_non_ops_files():
    roots = {"step"}
    opsmod = _tree("""
        def step(key):
            return jax.random.split(key)
    """)
    assert check_jit_purity(opsmod, "copycat_tpu/ops/consensus.py",
                            roots) == []
    impure = _tree("""
        import time

        def step(x):
            return time.time()
    """)
    assert check_jit_purity(impure, "copycat_tpu/models/bulk.py",
                            roots) == []


def test_jit_purity_decorated_roots_and_callbacks():
    tree = _tree("""
        import functools, jax

        @functools.partial(jax.jit, static_argnames=("k",))
        def topk(x, k):
            jax.debug.callback(print, x)
            return x
    """)
    roots = collect_jit_roots({"copycat_tpu/ops/pallas_kernels.py": tree})
    assert "topk" in roots
    found = check_jit_purity(tree, "copycat_tpu/ops/pallas_kernels.py",
                             roots)
    assert len(found) == 1 and "callback" in found[0].message


def test_live_ops_tree_is_pure(live_lint):
    assert [f for f in live_lint.findings if f.rule == "jit-purity"] == []


# ---------------------------------------------------------------------------
# engine: suppressions, baseline, cache, CLI
# ---------------------------------------------------------------------------


def test_suppression_scoping():
    src = ("import time\n"
           "async def f():\n"
           "    time.sleep(1)  # copycheck: ignore[loop-blocking] why\n"
           "    # copycheck: ignore[loop-blocking] next line\n"
           "    time.sleep(2)\n"
           "    time.sleep(3)\n")
    sups = scan_suppressions(src)
    tree = ast.parse(src)
    found = check_loop_blocking(tree, "m.py")
    assert len(found) == 3
    suppressed = [f for f in found if is_suppressed(f, sups)]
    assert {f.line for f in suppressed} == {3, 5}
    # a different rule on the same line is NOT suppressed
    other = Finding(rule="orphan-task", path="m.py", line=3, message="x")
    assert not is_suppressed(other, sups)
    # the documented wildcard covers every rule on its line
    wild = scan_suppressions("x()  # copycheck: ignore[*] escape hatch\n")
    assert is_suppressed(
        Finding(rule="orphan-task", path="m.py", line=1, message="x"), wild)


def _mini_repo(tmp_path, body):
    """A temp repo shaped like ours: package + a file with findings."""
    pkg = tmp_path / "copycat_tpu"
    (pkg / "utils").mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (pkg / "utils" / "__init__.py").write_text("")
    (pkg / "utils" / "knobs.py").write_text(KNOBS_SRC)
    (pkg / "mod.py").write_text(body)
    return tmp_path


def test_engine_baseline_carries_findings_and_reports_stale(tmp_path):
    root = _mini_repo(
        tmp_path, "async def f(loop, c):\n    loop.create_task(c)\n")
    result = run_lint(root=str(root), use_cache=False)
    assert len(result.findings) == 1
    bl = Baseline()
    bl.entries[result.findings[0].identity()] = "kept: test"
    bl.entries[("orphan-task", "copycat_tpu/gone.py", "f", "old")] = "stale"
    bl_path = str(tmp_path / "bl.json")
    bl.save(bl_path)
    result = run_lint(root=str(root), baseline_path=bl_path,
                      use_cache=False)
    assert result.findings == []
    assert len(result.baselined) == 1
    assert len(result.stale_baseline) == 1


def test_strict_fails_and_reports_stale_baseline(tmp_path):
    from copycat_tpu.analysis.engine import render_text

    root = _mini_repo(tmp_path, "async def f():\n    pass\n")
    bl = Baseline()
    bl.entries[("orphan-task", "copycat_tpu/gone.py", "f", "old")] = "gone"
    bl_path = str(tmp_path / "bl.json")
    bl.save(bl_path)
    result = run_lint(root=str(root), baseline_path=bl_path,
                      use_cache=False)
    assert result.findings == [] and len(result.stale_baseline) == 1
    # strict: status line and exit path agree (a stale entry is a FAIL)
    assert "copycheck: FAIL" in render_text(result, strict=True)
    assert "copycheck: ok" in render_text(result, strict=False)


def test_engine_cache_hits_and_invalidates(tmp_path):
    root = _mini_repo(
        tmp_path, "async def f(loop, c):\n    loop.create_task(c)\n")
    r1 = run_lint(root=str(root), use_cache=True)
    assert len(r1.findings) == 1
    cache_path = root / ".copycheck-cache.json"
    assert cache_path.exists()
    cached = json.loads(cache_path.read_text())
    assert "copycat_tpu/mod.py" in cached["files"]
    # warm hit returns identical findings
    r2 = run_lint(root=str(root), use_cache=True)
    assert [f.to_json() for f in r2.findings] == \
        [f.to_json() for f in r1.findings]
    # editing the file invalidates just that entry
    (root / "copycat_tpu" / "mod.py").write_text("async def f():\n    pass\n")
    r3 = run_lint(root=str(root), use_cache=True)
    assert r3.findings == []


def test_engine_cache_invalidates_per_rule_group(tmp_path, monkeypatch):
    """The v2 cache satellite: editing ONE rule module re-lints only its
    group — every other group's cached results survive."""
    from copycat_tpu.analysis import engine

    root = _mini_repo(
        tmp_path, "async def f(loop, c):\n    loop.create_task(c)\n")
    r1 = run_lint(root=str(root), use_cache=True)
    assert len(r1.findings) == 1

    import collections
    counts: collections.Counter = collections.Counter()
    for spec in engine.RULE_GROUPS:
        def counted(path, src, tree, ctx, _key=spec.key, _orig=spec.run):
            counts[_key] += 1
            return _orig(path, src, tree, ctx)

        monkeypatch.setattr(spec, "run", counted)

    # warm run: every group is a cache hit, nothing recomputes
    r2 = run_lint(root=str(root), use_cache=True)
    assert not counts
    assert [f.to_json() for f in r2.findings] == \
        [f.to_json() for f in r1.findings]

    # "edit" one rule module: exactly that group recomputes
    real = engine._analysis_source
    monkeypatch.setattr(
        engine, "_analysis_source",
        lambda mod: real(mod) + ("\n# edited" if mod == "rules_wire.py"
                                 else ""))
    r3 = run_lint(root=str(root), use_cache=True)
    assert set(counts) == {"wire"}
    assert [f.to_json() for f in r3.findings] == \
        [f.to_json() for f in r1.findings]


def test_sarif_emitter_levels_and_suppressions(tmp_path):
    from copycat_tpu.analysis.engine import render_sarif

    root = _mini_repo(tmp_path, (
        "async def f(loop, c):\n"
        "    loop.create_task(c)\n"
        "    loop.create_task(c)  # copycheck: ignore[orphan-task] test\n"))
    result = run_lint(root=str(root), use_cache=False)
    assert len(result.findings) == 1 and len(result.suppressed) == 1
    doc = json.loads(render_sarif(result))
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "copycheck"
    assert {"id": "orphan-task"} in run["tool"]["driver"]["rules"]
    live = [r for r in run["results"] if "suppressions" not in r]
    sup = [r for r in run["results"] if "suppressions" in r]
    assert len(live) == 1 and live[0]["level"] == "error"
    assert live[0]["ruleId"] == "orphan-task"
    loc = live[0]["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "copycat_tpu/mod.py"
    assert loc["region"]["startLine"] == 2
    assert live[0]["partialFingerprints"]["copycheckIdentity/v1"]
    assert len(sup) == 1
    assert sup[0]["suppressions"] == [{"kind": "inSource"}]
    assert sup[0]["level"] == "note"


def _git(root, *argv):
    subprocess.run(["git", "-c", "user.email=t@t", "-c", "user.name=t",
                    *argv], cwd=root, check=True, capture_output=True)


def test_changed_mode_filters_findings_to_the_diff(tmp_path):
    root = _mini_repo(
        tmp_path, "async def f(loop, c):\n    loop.create_task(c)\n")
    _git(root, "init", "-q")
    _git(root, "add", "-A")
    _git(root, "commit", "-qm", "seed")
    # an UNTRACKED module with a violation: the diff gate must see it
    (root / "copycat_tpu" / "fresh.py").write_text(
        "async def g(loop, c):\n    loop.create_task(c)\n")
    full = run_lint(root=str(root), use_cache=False)
    assert sorted(f.path for f in full.findings) == [
        "copycat_tpu/fresh.py", "copycat_tpu/mod.py"]
    diff = run_lint(root=str(root), use_cache=False, changed_base="HEAD")
    assert diff.changed_files == ["copycat_tpu/fresh.py"]
    # the committed file's finding is out of scope; analysis still ran
    # package-wide (files count is the whole tree)
    assert [f.path for f in diff.findings] == ["copycat_tpu/fresh.py"]
    assert diff.files == full.files


def test_changed_mode_uses_merge_base_not_two_dot(tmp_path):
    # a branch BEHIND the base rev must not inherit files only the
    # base's own history changed (two-dot `git diff BASE` would)
    root = _mini_repo(
        tmp_path, "async def f(loop, c):\n    loop.create_task(c)\n")
    _git(root, "init", "-q", "-b", "main")
    _git(root, "add", "-A")
    _git(root, "commit", "-qm", "seed")
    _git(root, "branch", "feature")
    # main moves ahead with its own violating module...
    (root / "copycat_tpu" / "mainonly.py").write_text(
        "async def m(loop, c):\n    loop.create_task(c)\n")
    _git(root, "add", "-A")
    _git(root, "commit", "-qm", "main moves on")
    # ...while the PR branch (behind main) adds just its own file
    _git(root, "checkout", "-q", "feature")
    (root / "copycat_tpu" / "fresh.py").write_text(
        "async def g(loop, c):\n    loop.create_task(c)\n")
    diff = run_lint(root=str(root), use_cache=False,
                    changed_base="main")
    assert diff.changed_files == ["copycat_tpu/fresh.py"]
    assert [f.path for f in diff.findings] == ["copycat_tpu/fresh.py"]


def test_write_baseline_refuses_changed_scope(tmp_path, capsys):
    from copycat_tpu.analysis.engine import main as lint_main

    with pytest.raises(SystemExit) as exc:
        lint_main(["--write-baseline", "--changed", "HEAD"])
    assert exc.value.code == 2
    assert "--write-baseline needs the full-tree view" in \
        capsys.readouterr().err


def test_cli_lint_exit_codes(tmp_path):
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)  # the lint path never needs jax
    clean = subprocess.run(
        [sys.executable, "-m", "copycat_tpu.analysis", "--strict",
         "--no-cache"],
        cwd=REPO, env=env, capture_output=True, text=True)
    assert clean.returncode == 0, clean.stdout + clean.stderr
    assert "copycheck: ok" in clean.stdout
    # a seeded violation flips the exit code
    bad = tmp_path / "bad_raft.py"
    bad.write_text("async def f(loop, c):\n    loop.create_task(c)\n")
    dirty = subprocess.run(
        [sys.executable, "-m", "copycat_tpu.analysis", "--no-cache",
         str(bad)],
        cwd=REPO, env=env, capture_output=True, text=True)
    assert dirty.returncode == 1
    assert "orphan-task" in dirty.stdout


def test_all_rules_have_coverage_here():
    """Every rule name is exercised by at least one seeded violation in
    this file — a new rule without a fixture test fails the suite."""
    src = open(__file__, encoding="utf-8").read()
    for rule in ALL_RULES:
        assert rule in src, f"rule {rule} has no fixture coverage"


def test_update_golden_roundtrip(tmp_path, monkeypatch):
    # regeneration produces exactly the committed artifact (idempotent)
    committed = open(os.path.join(REPO, "tests", "golden",
                                  "wire_schema.json")).read()
    import shutil

    root = tmp_path / "repo"
    (root / "copycat_tpu" / "protocol").mkdir(parents=True)
    shutil.copy(os.path.join(REPO, "copycat_tpu", "protocol",
                             "messages.py"),
                root / "copycat_tpu" / "protocol" / "messages.py")
    path = update_wire_golden(root=str(root))
    assert open(path).read() == committed

"""LaserEVM: the symbolic-execution engine (capability parity:
mythril/laser/ethereum/svm.py:43-783 — worklist + strategy loop,
multi-transaction driver with reachability pruning, plugin hook channels,
per-opcode pre/post hooks, CFG bookkeeping, create/execution timeouts).

In this build the engine additionally hosts the TPU pre-filter seam: when
`support_args.args.tpu_prefilter` is on, open-state reachability pruning
batches all open-state constraint systems through the interval lane pruner
before falling back to per-state solver checks (see
mythril_tpu/models/pruner.py)."""

import logging
import os
import random
import sys
import time
from abc import ABCMeta
from collections import defaultdict
from copy import copy
from datetime import datetime, timedelta
from typing import Callable, Dict, List, Optional, Tuple

from ..smt import symbol_factory
from ..support import gc_schedule
from ..support.opcodes import OPCODES
from ..support.support_args import args
from ..support.telemetry import trace
from .cfg import Edge, JumpType, Node, NodeFlags
from .evm_exceptions import StackUnderflowException, VmException
from .instruction_data import get_required_stack_elements
from .instructions import Instruction
from .plugin.signals import PluginSkipState, PluginSkipWorldState
from .execution_info import ExecutionInfo
from .state.global_state import GlobalState
from .state.world_state import WorldState
from .strategy.basic import DepthFirstSearchStrategy
from .time_handler import time_handler
from .transaction import (
    ContractCreationTransaction,
    TransactionEndSignal,
    TransactionStartSignal,
    execute_contract_creation,
    execute_message_call,
)

log = logging.getLogger(__name__)


def _loopsum_declined(gs) -> bool:
    """Does this state carry a loop-summary decline marker
    (analysis/static_pass/loop_summary.LoopsumDecline)?  Lazy import:
    the sweep must stay importable with the static pass stripped."""
    try:
        from ..analysis.static_pass import loop_summary

        return loop_summary.state_declined(gs)
    except Exception:
        return False


class LaserEVM:
    """The symbolic EVM engine: explores the state space of a contract
    over a sequence of symbolic transactions."""

    def __init__(
        self,
        dynamic_loader=None,
        max_depth=float("inf"),
        execution_timeout=60,
        create_timeout=10,
        strategy=DepthFirstSearchStrategy,
        transaction_count=2,
        requires_statespace=True,
        iprof=None,
        use_reachability_check=True,
        beam_width=None,
    ) -> None:
        self.execution_info: List[ExecutionInfo] = []

        self.open_states: List[WorldState] = []
        self.total_states = 0
        self.dynamic_loader = dynamic_loader
        self.use_reachability_check = use_reachability_check

        self.work_list: List[GlobalState] = []
        self.strategy = strategy(
            self.work_list, max_depth, beam_width=beam_width
        )
        self.max_depth = max_depth
        self.transaction_count = transaction_count

        self.execution_timeout = execution_timeout or 0
        self.create_timeout = create_timeout or 0

        self.requires_statespace = requires_statespace
        if self.requires_statespace:
            self.nodes: Dict[int, Node] = {}
            self.edges: List[Edge] = []

        self.time: Optional[datetime] = None
        self.executed_transactions: bool = False
        # test/bench rig: seconds slept per completed top-level path
        # (corpus steal smokes and migration tests model per-path
        # solver/device latency with it so work REDISTRIBUTION is
        # observable on a single shared CPU; see docs/work_stealing.md)
        self._path_delay = float(
            os.environ.get("MTPU_PATH_DELAY", "0") or 0)
        # checkpoint/resume seam (support/checkpoint.py): first unrun
        # round, and the per-round snapshot callback
        self.start_round: int = 0
        self.checkpoint_sink: Optional[Callable] = None
        # live lane-plane resume (docs/checkpoint.md): in-flight
        # GlobalStates restored from a checkpoint finish their
        # interrupted round before the round loop continues; the round
        # context below is what a SIGTERM/fatal live dump stamps its
        # checkpoint with (next unrun round, tx count, address)
        self._resume_inflight: Optional[List[GlobalState]] = None
        self._ckpt_round_ctx: Optional[tuple] = None
        self._ckpt_current_state: Optional[GlobalState] = None
        # static pre-analysis round context (docs/static_pass.md):
        # True while the CURRENT message-call round is the run's last —
        # its open states seed nothing, so a statically-dead state may
        # retire even when a terminator is reachable (if nothing is
        # pending on it). Defaults conservative.
        self._static_final_tx: bool = False

        self.pre_hooks: Dict[str, List[Callable]] = defaultdict(list)
        self.post_hooks: Dict[str, List[Callable]] = defaultdict(list)

        self._add_world_state_hooks: List[Callable] = []
        self._execute_state_hooks: List[Callable] = []
        self._start_exec_trans_hooks: List[Callable] = []
        self._stop_exec_trans_hooks: List[Callable] = []
        self._start_sym_trans_hooks: List[Callable] = []
        self._stop_sym_trans_hooks: List[Callable] = []
        self._start_sym_exec_hooks: List[Callable] = []
        self._stop_sym_exec_hooks: List[Callable] = []
        self._start_exec_hooks: List[Callable] = []
        self._stop_exec_hooks: List[Callable] = []
        self._transaction_end_hooks: List[Callable] = []
        self._lane_coverage_hooks: List[Callable] = []

        self.iprof = iprof
        self.instr_pre_hook: Dict[str, List[Callable]] = {}
        self.instr_post_hook: Dict[str, List[Callable]] = {}
        for op in OPCODES:
            self.instr_pre_hook[op] = []
            self.instr_post_hook[op] = []
        self.hook_type_map = {
            "start_execute_transactions": self._start_exec_trans_hooks,
            "stop_execute_transactions": self._stop_exec_trans_hooks,
            "add_world_state": self._add_world_state_hooks,
            "execute_state": self._execute_state_hooks,
            "start_sym_exec": self._start_sym_exec_hooks,
            "stop_sym_exec": self._stop_sym_exec_hooks,
            "start_sym_trans": self._start_sym_trans_hooks,
            "stop_sym_trans": self._stop_sym_trans_hooks,
            "start_exec": self._start_exec_hooks,
            "stop_exec": self._stop_exec_hooks,
            "transaction_end": self._transaction_end_hooks,
            "lane_coverage": self._lane_coverage_hooks,
        }
        log.info(
            "LASER EVM initialized with dynamic loader: %s", dynamic_loader
        )

    def extend_strategy(self, extension: ABCMeta, **kwargs) -> None:
        self.strategy = extension(self.strategy, **kwargs)

    # -- top-level drivers --------------------------------------------------

    def sym_exec(
        self,
        world_state: WorldState = None,
        target_address: int = None,
        creation_code: str = None,
        contract_name: str = None,
    ) -> None:
        """Run symbolic execution: either against a preconfigured world
        state + target address, or from creation code."""
        pre_configuration_mode = target_address is not None
        scratch_mode = (
            creation_code is not None and contract_name is not None
        )
        if pre_configuration_mode == scratch_mode:
            raise ValueError(
                "Symbolic execution started with invalid parameters"
            )
        # freeze the warmed heap once the process stops compiling
        gc_schedule.before_analysis()
        # the whole symbolic execution of one contract (B/E: the body
        # keeps its shape; an exception leaves the B unmatched)
        trace.begin("svm.sym_exec")

        log.debug("Starting LASER execution")
        for hook in self._start_sym_exec_hooks:
            hook()

        time_handler.start_execution(self.execution_timeout)
        self.time = datetime.now()

        if pre_configuration_mode:
            self.open_states = [world_state]
            log.info(
                "Starting message call transaction to %s", target_address
            )
            self.execute_transactions(
                symbol_factory.BitVecVal(target_address, 256)
            )
        elif scratch_mode:
            log.info("Starting contract creation transaction")
            created_account = execute_contract_creation(
                self, creation_code, contract_name, world_state=world_state
            )
            log.info(
                "Finished contract creation, found %d open states",
                len(self.open_states),
            )
            if len(self.open_states) == 0:
                log.warning(
                    "No contract was created during the execution of "
                    "contract creation. Increase the resources for "
                    "creation execution (--max-depth or --create-timeout) "
                    "or use the --bin-runtime flag."
                )
            self.execute_transactions(created_account.address)

        log.info("Finished symbolic execution")
        if self.requires_statespace:
            log.info(
                "%d nodes, %d edges, %d total states",
                len(self.nodes),
                len(self.edges),
                self.total_states,
            )
        for hook in self._stop_sym_exec_hooks:
            hook()
        trace.end("svm.sym_exec")

    def resume_exec(self, open_states, address, start_round: int,
                    inflight=None) -> None:
        """Continue a checkpointed analysis: restored open states, the
        original target address, and the first UNRUN transaction round
        (support/checkpoint.py owns the snapshot format). ``inflight``
        is the live lane plane of a mid-round checkpoint — states
        mid-way through round ``start_round - 1`` that finish that
        round first (docs/checkpoint.md)."""
        log.info("Resuming symbolic execution at round %d (%d "
                 "in-flight states)", start_round,
                 len(inflight or ()))
        for hook in self._start_sym_exec_hooks:
            hook()
        time_handler.start_execution(self.execution_timeout)
        self.time = datetime.now()
        self.open_states = list(open_states)
        self.start_round = start_round
        self._resume_inflight = list(inflight) if inflight else None
        if isinstance(address, int):
            address = symbol_factory.BitVecVal(address, 256)
        self.execute_transactions(address)
        for hook in self._stop_sym_exec_hooks:
            hook()

    def execute_transactions(self, address) -> None:
        for hook in self._start_exec_trans_hooks:
            hook()
        if self.executed_transactions is False:
            self._execute_transactions(address)
        for hook in self._stop_exec_trans_hooks:
            hook()

    def _execute_transactions(self, address):
        """Execute transaction_count message calls against `address` from
        all open states, pruning unreachable open states between rounds.
        `start_round` skips completed rounds (checkpoint resume); the
        `checkpoint_sink` callback fires after each completed round with
        (next round index, open states, concrete target address)."""
        self.time = datetime.now()
        # live-plane resume (docs/checkpoint.md): in-flight states of
        # round start_round-1 finish that round FIRST — their end
        # states join open_states before the loop re-seeds
        if self._resume_inflight:
            inflight, self._resume_inflight = self._resume_inflight, None
            self._finish_inflight_round(address, inflight)
        for i in range(self.start_round, self.transaction_count):
            if len(self.open_states) == 0:
                break
            old_states_count = len(self.open_states)
            if self.use_reachability_check:
                self.open_states = self._prune_unreachable_states(
                    self.open_states
                )
                prune_count = old_states_count - len(self.open_states)
                if prune_count:
                    log.info(
                        "Pruned %d unreachable states", prune_count
                    )
            log.info(
                "Starting message call transaction, iteration: %d, "
                "%d initial states",
                i,
                len(self.open_states),
            )
            # svm-round span (docs/observability.md): B/E pair rather
            # than a `with` block so the round body keeps its shape;
            # an exception mid-round leaves the B unmatched, which
            # Perfetto closes at trace end (and the flight recorder
            # captures the crash anyway)
            trace.begin("svm.round", round=i,
                        states=len(self.open_states))
            func_hashes = (
                args.transaction_sequences[i]
                if args.transaction_sequences
                else None
            )
            if func_hashes:
                for itr, func_hash in enumerate(func_hashes):
                    if func_hash in (-1, -2):
                        func_hashes[itr] = func_hash
                    else:
                        func_hashes[itr] = bytes.fromhex(
                            hex(func_hash)[2:].zfill(8)
                        )
            # static-retire round context: open states of the LAST
            # round seed nothing (docs/static_pass.md)
            self._static_final_tx = i + 1 >= self.transaction_count
            # static tx-sequence pruning (docs/static_pass.md): an
            # open state that finished the previous round inside
            # function f skips next-round functions g the
            # interprocedural dependence relation proves blind to f's
            # effects — the entry wave appends selector-exclusion
            # constraints per state (transaction/entry.py). Stands
            # down when the caller pinned explicit sequences.
            if func_hashes is None:
                self._static_tx_prune_screen(address)
            # round context for the migration bus's MID-ROUND yield
            # (parallel/migrate.py): states finishing round i await
            # round i+1, so a slice exported while round i still runs
            # resumes at i+1 on the thief. The same tuple stamps a
            # SIGTERM/fatal live dump (support/checkpoint.py).
            self._ckpt_round_ctx = (i + 1, self.transaction_count,
                                    address)
            bus = getattr(args, "migration_bus", None)
            if bus is not None:
                bus.begin_round(i + 1, self.transaction_count, address)
            for hook in self._start_sym_trans_hooks:
                hook()
            execute_message_call(self, address, func_hashes=func_hashes)
            for hook in self._stop_sym_trans_hooks:
                hook()
            # round-boundary open-state merge (laser/merge.py,
            # MTPU_MERGE): the drained worklist collapses exact-
            # frontier twins under an OR'd constraint suffix and
            # retires implied siblings BEFORE the next round re-seeds
            # from it — fewer states to screen, solve and execute.
            # Final-round states are left untouched (nothing re-seeds
            # from them).
            if i + 1 < self.transaction_count and \
                    len(self.open_states) > 1:
                try:
                    from .merge import merge_open_states

                    self.open_states = merge_open_states(
                        self.open_states)
                except Exception as e:  # a screen, never an error path
                    log.debug("open-state merge failed: %s", e)
            if (self.use_reachability_check
                    and i + 1 < self.transaction_count):
                # fully-async feasibility seam: round i+1's open-state
                # screen starts NOW and is collected at the round top
                # (no-op when the solver pool is serial)
                self._screen_prefetch = self._submit_open_state_screen()
            if self.checkpoint_sink is not None:
                self.checkpoint_sink(i + 1, self.open_states, address)
            # cross-run warm store round sink (support/warm_store.py):
            # the banks proved so far persist under the analyzed
            # code's hash, so a preempted run still warms the next
            # submission. Inert unless a store is active.
            try:
                from ..support import warm_store

                warm_store.round_sink()
            except Exception as e:  # best-effort, never the analysis
                log.debug("warm-store round sink failed: %s", e)
            # cross-host path-batch migration (parallel/migrate.py):
            # a drained corpus rank can take half this round's open
            # states; the bus trims self.open_states in place
            bus = getattr(args, "migration_bus", None)
            if bus is not None:
                bus.on_round_end(self, i + 1, self.transaction_count,
                                 address)
            trace.end("svm.round",
                      open_states=len(self.open_states))
        self.start_round = 0  # a later sym_exec must not skip rounds
        self._ckpt_round_ctx = None
        self.executed_transactions = True

    def _finish_inflight_round(self, address, inflight) -> None:
        """Finish an interrupted transaction round from its restored
        in-flight lane plane (docs/checkpoint.md): the states enter
        the worklist mid-transaction exactly where the checkpoint cut
        them — the lane sweep re-materializes device-seedable ones
        into its own plane at the next window boundary, the host loop
        continues the rest — and their end states join open_states for
        the normal loop at ``start_round``. Hook pairs fire like any
        round's, so plugin bookkeeping stays balanced."""
        i = max(self.start_round - 1, 0)
        log.info("finishing interrupted round %d from %d in-flight "
                 "states", i, len(inflight))
        trace.begin("ckpt.resume", round=i, inflight=len(inflight))
        self._static_final_tx = i + 1 >= self.transaction_count
        self._ckpt_round_ctx = (i + 1, self.transaction_count, address)
        bus = getattr(args, "migration_bus", None)
        if bus is not None:
            bus.begin_round(i + 1, self.transaction_count, address)
        for hook in self._start_sym_trans_hooks:
            hook()
        self.work_list.extend(inflight)
        self.exec()
        for hook in self._stop_sym_trans_hooks:
            hook()
        if bus is not None:
            bus.on_round_end(self, i + 1, self.transaction_count,
                             address)
        try:
            from ..smt.solver.solver_statistics import SolverStatistics

            SolverStatistics().bump(resume_rounds=1,
                                    lanes_imported=len(inflight))
        except Exception:  # telemetry only
            pass
        trace.end("ckpt.resume", open_states=len(self.open_states))

    def _static_tx_prune_screen(self, address) -> None:
        """Pre-round static independence screen (docs/static_pass.md,
        deps.excluded_selectors): per open state, selectors the next
        transaction may skip because the previous transaction's
        function provably cannot influence them. The exclusions are
        stashed on the world state; EntryWave.spawn_call turns them
        into calldata constraints. Counted as ``static_tx_prunes``.
        Sound per the two-rule argument in deps.py — final-round
        orderings are redundant duplicates of the sibling branch that
        ran g from f's pre-state, non-final orderings only prune one
        side of a provably commuting pair."""
        try:
            from ..analysis import static_pass
            from ..analysis.static_pass import deps as deps_mod

            if not static_pass.taint_enabled():
                return
            total = 0
            final = bool(self._static_final_tx)
            for ws in self.open_states:
                try:
                    ws._mtpu_excluded_selectors = None
                    account = ws[address]
                    info = static_pass.info_for_code_obj(account.code)
                    if info is None:
                        continue
                    deps_mod.register_code(info)  # fact-seeding gate
                    prev = getattr(ws, "_mtpu_last_fentry", None)
                    excl = deps_mod.excluded_selectors(info, prev, final)
                    if excl:
                        ws._mtpu_excluded_selectors = excl
                        total += len(excl)
                except Exception:
                    continue
            if total:
                from ..smt.solver.solver_statistics import (
                    SolverStatistics,
                )

                SolverStatistics().bump(static_tx_prunes=total)
                log.info("static independence screen excluded %d "
                         "tx-pair orderings this round", total)
        except Exception as e:  # a screen, never an error path
            log.debug("static tx-prune screen failed: %s", e)

    def _submit_open_state_screen(self):
        """Round-boundary async reachability prefetch
        (docs/solver_pool.md): with the solver pool parallel the next
        round's open-state screen is submitted as soon as this round's
        states are final (right after the stop-transaction hooks), so
        its solver wall runs behind the checkpoint sink, the migration
        bus round-end and the per-round bookkeeping instead of
        serializing in front of the next round. Returns None when the
        pool is serial — the screen then runs synchronously at the
        round top, exactly as before."""
        from ..smt.solver import pool as pool_mod

        if not self.open_states or not pool_mod.get_pool().parallel:
            return None
        snapshot = list(self.open_states)
        return (snapshot,
                pool_mod.get_pool().submit_async(
                    lambda: self._screen_open_states(snapshot)))

    def _prune_unreachable_states(self, open_states):
        """Reachability filter over open states (the screen itself is
        _screen_open_states; a round-boundary prefetch may have already
        run it — its verdicts are used only when the state list is
        unchanged, element-identical, since the submit)."""
        prefetch = getattr(self, "_screen_prefetch", None)
        self._screen_prefetch = None
        if prefetch is not None:
            snapshot, fut = prefetch
            if len(snapshot) == len(open_states) and all(
                    a is b for a, b in zip(snapshot, open_states)):
                try:
                    return fut.result()
                except Exception as e:
                    log.debug("async open-state screen failed: %s", e)
            # list changed since submit (e.g. the migration bus took a
            # slice): redo synchronously — the background run banked
            # its proofs in the verdict cache, so the redo is mostly
            # exact-key hits
        return self._screen_open_states(open_states)

    def _screen_open_states(self, open_states):
        """The reachability screen body. With the TPU pre-filter
        enabled, interval-infeasible states are dropped in batch before
        any solver query — and with MTPU_PROPAGATE on (the default)
        that screen is the bidirectional product-domain fixpoint
        (ops/propagate.py): known-bits x interval kills the forward
        pass cannot make, plus harvested facts that hint the surviving
        check_batch solves (docs/propagation.md)."""
        with trace.span("svm.open_state_screen",
                        n=len(open_states)):
            return self._screen_open_states_inner(open_states)

    def _screen_open_states_inner(self, open_states):
        if args.tpu_prefilter:
            try:
                from ..models.pruner import prefilter_world_states

                open_states = prefilter_world_states(open_states)
            except Exception as e:  # never let the fast path break the run
                from ..support.devices import note_device_error

                note_device_error("device_prefilter_errors",
                                  "open-state prefilter", e)
        if open_states:
            # batched discharge: sibling open states share long
            # constraint prefixes (they forked from common JUMPIs), so
            # one trie-ordered pass over the incremental session
            # replaces per-state from-scratch solves; verdict semantics
            # are identical to is_possible (support/model.check_batch).
            # Single-state rounds route through the same seam so the
            # run-wide verdict cache (smt/solver/verdicts.py) answers
            # prefixes already proved in earlier rounds and windows.
            from ..support.model import check_batch

            keep = check_batch([s.constraints for s in open_states])
            return [s for s, ok in zip(open_states, keep) if ok]
        return open_states

    # -- timeouts -----------------------------------------------------------

    def _check_create_termination(self) -> bool:
        if len(self.open_states) != 0:
            return (
                self.create_timeout > 0
                and self.time + timedelta(seconds=self.create_timeout)
                <= datetime.now()
            )
        return self._check_execution_termination()

    def _check_execution_termination(self) -> bool:
        return (
            self.execution_timeout > 0
            and self.time + timedelta(seconds=self.execution_timeout)
            <= datetime.now()
        )

    # -- the hot loop -------------------------------------------------------

    def _lane_engine_sweep(self, min_batch: int = 1) -> None:
        """Run tx-entry worklist states through the TPU lane engine
        (laser/lane_engine.py): the device executes the symbolic
        ALU/stack/memory/storage/jump core of every path in batch, forks
        on symbolic JUMPIs, and hands back states parked at the first
        instruction it cannot model. The host loop below continues from
        those, so hooks/detectors/transaction semantics are unchanged
        for everything host-executed."""
        # the sweep's host work before each explore (B/E: closed
        # before every return and before each group's explore)
        trace.begin("svm.sweep_prep")
        from .lane_engine import (
            LaneEngine,
            code_to_bytes,
            lane_seedable,
        )

        # every opcode with a registered hook must park device-side so
        # the hook fires on the host — unless the hook's module has a
        # lane adapter (analysis/module/lane_adapters.py) that lifts it:
        # those hooks are served at drain time instead, which keeps the
        # device forking/executing on the hot opcodes the taint modules
        # hook (JUMPI, arithmetic, SSTORE). Universal per-instruction
        # hooks disable the sweep outright — except telemetry-only ones
        # (marked lane_engine_safe, e.g. the instruction profiler's).
        def _essential(hooks):
            return [h for h in hooks
                    if not getattr(h, "lane_engine_safe", False)]

        if any(_essential(h) for h in self.instr_pre_hook.values()) \
                or any(_essential(h)
                       for h in self.instr_post_hook.values()):
            trace.end("svm.sweep_prep")
            return
        try:
            from ..analysis.module.lane_adapters import get_adapter
        except Exception:  # pragma: no cover
            get_adapter = lambda m: None  # noqa: E731
        # drain-fired issues flow through module.issues; when the
        # issue-annotation mode diverts them onto states, lifted hooks
        # would lose their issues — keep everything parked instead
        can_lift = not args.use_issue_annotations
        if not can_lift and args.tpu_lanes:
            log.info(
                "lane-mode fallback active: --use-issue-annotations "
                "diverts drain-fired issues onto states, so detector "
                "hook lifting is disabled and hooked opcodes park "
                "host-side (documented in PARITY.md)")
        adapters: List[object] = []
        blocked = set()
        for hook_dict in (self.pre_hooks, self.post_hooks):
            for opname, hooks in hook_dict.items():
                for h in _essential(hooks):
                    ad = get_adapter(getattr(h, "__self__", None)) \
                        if can_lift else None
                    if ad is not None and opname in ad.lifted_hooks:
                        if ad not in adapters:
                            adapters.append(ad)
                    else:
                        blocked.add(opname)
        if "JUMPI" in blocked:
            # a hook without an adapter pins every branch to the host:
            # the device cannot fork, so batching buys nothing
            log.info("lane engine idle: JUMPI hooked without an adapter")
            trace.end("svm.sweep_prep")
            return
        from ..ops import symstep as _symstep

        table = _symstep.SYM_EXECUTABLE.copy()
        from .lane_engine import _OPB as _opb

        for name in blocked:
            if name in _opb:
                table[_opb[name]] = False
        code_of: Dict[int, bytes] = {}

        def _device_ok(gs: GlobalState) -> bool:
            # memoized on the state: a queued state does not mutate
            # between sweeps, and the periodic re-sweep otherwise
            # re-pays lane_seedable's stack/memory scans for the whole
            # worklist (terminal storms re-scan every parked state).
            # The memo does not survive GlobalState.__copy__ (fresh
            # __dict__), so post-step descendants re-evaluate.
            cached = gs.__dict__.get("_lane_verdict")
            if cached is not None:
                code = cached
                if code is False:
                    return False
                code_of[id(gs)] = code
                return True
            # a loop-summary DECLINE pins the family host-side: its
            # loop would otherwise pay a park/materialize round trip
            # per iteration at the device's summarizable-head plane
            # (docs/static_pass.md, MTPU_LOOPSUM)
            if _loopsum_declined(gs):
                gs._lane_verdict = False
                return False
            code = code_to_bytes(gs.environment.code)
            if code and lane_seedable(gs, exec_table=table):
                code_of[id(gs)] = code
                gs._lane_verdict = code
                return True
            gs._lane_verdict = False
            return False

        # count first, drain only on commitment: a drain-and-put-back
        # would reorder the work list under the strategy. Verdicts are
        # memoized so the drain pass doesn't re-pay lane_seedable's
        # per-state scans.
        verdict = {id(gs): _device_ok(gs) for gs in self.work_list}
        if sum(verdict.values()) < min_batch:
            trace.end("svm.sweep_prep")
            return  # device round trips don't pay for a trickle
        eligible = self.strategy.drain_eligible(
            lambda gs: verdict[id(gs)])
        groups: Dict[bytes, List[GlobalState]] = {}
        for gs in eligible:
            groups.setdefault(code_of[id(gs)], []).append(gs)
        # engines persist across sweeps/transactions: the device state
        # pool, object table, and term memos all stay warm (a fresh
        # engine per sweep pays the init dispatch + cold caches)
        cache = getattr(self, "_lane_engines", None)
        if cache is None:
            cache = self._lane_engines = {}
        from .lane_engine import (
            DEFAULT_STEP_BUDGET, DEFAULT_WINDOW, pick_mesh, pick_width,
            warm_variant,
        )

        # no ESSENTIAL hook on STOP — on EITHER channel: the
        # instruction channel (instr_pre/post_hook, fired inside
        # Instruction.evaluate) AND the detector channel (pre/post_
        # hooks, fired via _execute_pre_hook; unchecked_retval and the
        # integer module watch STOP there) — means a lane-retired
        # top-level STOP state can take the transaction-end shortcut
        # (_fast_terminal) and its materialization can skip the
        # stack/memory rebuild the STOP path never reads (lane_engine
        # slim_stop)
        slim_stop = (
            not _essential(self.instr_pre_hook["STOP"])
            and not _essential(self.instr_post_hook["STOP"])
            and not _essential(self.pre_hooks.get("STOP", []))
            and not _essential(self.post_hooks.get("STOP", []))
        )

        # static pre-analysis run context (docs/static_pass.md): the
        # active-detector mask derives from the registered detector
        # hooks' owning modules — exactly the set whose issues this run
        # can mint. The issue-annotation mode diverts issues onto
        # states, so the retire screen stays off there (a retired
        # state could carry an undelivered issue).
        static_mask = None
        static_patch_ok = False
        static_module_names = None
        try:
            from ..analysis import static_pass

            if static_pass.enabled() and can_lift:
                from ..analysis.module.base import DetectionModule

                active_mods = {
                    h.__self__
                    for hook_dict in (self.pre_hooks, self.post_hooks)
                    for hooks in hook_dict.values()
                    for h in hooks
                    if isinstance(getattr(h, "__self__", None),
                                  DetectionModule)
                }
                # a run with NO detection modules registered is not an
                # analysis run — its product is the explored state
                # space itself (open states, coverage, statespace), so
                # the retire screen must stand down entirely rather
                # than treat "no detectors" as "everything is dead"
                if active_mods:
                    static_mask = int(
                        static_pass.active_mask_for_modules(
                            active_mods))
                    static_patch_ok = all(
                        type(m).__name__ != "ArbitraryJump"
                        for m in active_mods)
                    # taint-refined planes key on the module set
                    # (docs/static_pass.md): refined_plane serves it
                    # only when every module's trigger semantics are
                    # known, and returns None otherwise
                    static_module_names = frozenset(
                        type(m).__name__ for m in active_mods)
        except Exception as e:
            log.debug("static pass context unavailable: %s", e)
        static_final = bool(self._static_final_tx)
        trace.end("svm.sweep_prep")

        for code, states in groups.items():
            trace.begin("svm.sweep_prep", states=len(states))
            # width right-sizing: args.tpu_lanes is the CAP; the engine
            # runs at the smallest bucket that fits this batch with
            # fork headroom (narrow planes = cheap init, transfers and
            # per-window compute on small analyses). When the desired
            # width's jit variant is still compiling in another thread,
            # fall back to the widest warm narrower bucket rather than
            # to the host interpreter.
            width = pick_width(args.tpu_lanes, len(states), code)
            if width > 64 and all(
                s.mstate.pc != 0 for s in states
            ):
                # a wave of RESUMED mid-path states (spill/refill
                # churn) sizes to the wave with fork headroom, not to
                # the code's full fork-scale history: an overflowing
                # tree's reseed waves ran ~1k live lanes on full-width
                # planes (~3% occupancy) and paid the whole per-step
                # width cost. If such a wave still forks wide it
                # spills again and the NEXT wave grows geometrically —
                # bounded churn. Routed through pick_width with
                # code=None (history ignored — that IS the intent) so
                # bucket rounding and FORCE_WIDTH pinning stay in one
                # place; halved headroom because resumed states mostly
                # run OUT rather than fan out.
                width = min(width,
                            pick_width(args.tpu_lanes, len(states),
                                       headroom=4))
            # each width warms on the mesh its engine will run on
            while width > 64 and not warm_variant(
                    width, len(code), {},
                    DEFAULT_WINDOW, DEFAULT_STEP_BUDGET,
                    mesh=pick_mesh(width)):
                width //= 2
            mesh = pick_mesh(width)
            if not warm_variant(width, len(code), {},
                                DEFAULT_WINDOW, DEFAULT_STEP_BUDGET,
                                mesh=mesh):
                self.work_list.extend(states)
                trace.end("svm.sweep_prep")
                continue
            key = (code, width,
                   mesh.devices.size if mesh is not None else 0,
                   frozenset(blocked),
                   tuple(id(a) for a in adapters), slim_stop)
            try:
                engine = cache.get(key)
                if engine is None:
                    engine = LaneEngine(n_lanes=width,
                                        blocked_ops=blocked,
                                        adapters=adapters,
                                        mesh=mesh,
                                        slim_stop=slim_stop)
                    cache[key] = engine
                    # keep at most two widths per code: drop the
                    # narrowest surplus engine (its pooled device
                    # planes stay in the bounded global pool)
                    same = [k for k in cache
                            if k[0] == code and k[3:] == key[3:]]
                    if len(same) > 2:
                        # evict the narrowest (width, mesh) variant
                        del cache[min(same, key=lambda k: (k[1], k[2]))]
                engine.static_active_mask = static_mask
                engine.static_final_tx = static_final
                engine.static_jump_patch_ok = static_patch_ok
                engine.static_module_names = static_module_names
                # mid-flight wave export (docs/checkpoint.md): the
                # migration bus can take the tail of a live device
                # wave at any window boundary; None when no bus or
                # live checkpointing is off (MTPU_CKPT=0)
                engine.export_client = None
                bus_mig = getattr(args, "migration_bus", None)
                if bus_mig is not None:
                    try:
                        engine.export_client = \
                            bus_mig.lane_export_client()
                    except Exception:
                        engine.export_client = None
                # cross-tenant wave packing (laser/wave_pack.py): a
                # pack-member analysis routes its wave through the
                # group coordinator — co-scheduled members' lanes fold
                # into ONE packed dispatch, solo waves run this very
                # engine unchanged. None outside pack-member threads.
                from .wave_pack import current_client

                _pack_client = current_client()
                trace.end("svm.sweep_prep")
                # the call into the lane engine: its own spans name the
                # window loop; this one, the set-up and teardown around
                # it (memo resets, coverage pull, freeing the explore)
                with trace.span("svm.sweep_explore", states=len(states)):
                    if _pack_client is not None:
                        parked = _pack_client.explore(self, engine, code,
                                                      states)
                    else:
                        parked = engine.explore(code, states)
            except Exception as e:  # any failure falls back to host
                from ..support.devices import note_device_error

                note_device_error("device_explore_errors",
                                  f"lane engine sweep at {width} lanes",
                                  e)
                self.work_list.extend(states)
                # capacity autoprobe (docs/drain_pipeline.md): on the
                # first kernel-fault fallback, bisect the max stable
                # live width once and clamp pick_width (persisted via
                # cost_model into stats.json) — subsequent sweeps and
                # runs degrade through spill/refill instead of
                # re-faulting. A width that re-probes clean clamps
                # nothing (transient failure, not capacity).
                try:
                    from .lane_engine import note_kernel_fault

                    note_kernel_fault(width)
                except Exception:
                    pass
                continue
            # the sweep's host work after the explore (B/E, closed at
            # the end of the group)
            trace.begin("svm.sweep_retire", parked=len(parked))
            if static_mask is not None:
                # host-side twin of the window-boundary retire: parked
                # states that are statically dead never re-enter the
                # worklist (same soundness test, docs/static_pass.md)
                try:
                    from ..analysis import static_pass

                    parked = static_pass.screen_states(
                        parked, static_mask, static_final,
                        module_names=static_module_names)
                except Exception as e:
                    log.debug("static state screen failed: %s", e)
            # verified loop-summary application (docs/static_pass.md,
            # MTPU_LOOPSUM): lanes park at summarizable heads — apply
            # the closed form here so applied states re-enter the
            # worklist already AT the loop exit (and bound-exceeded
            # instances retire without re-executing), instead of
            # round-tripping through the strategy at the head
            try:
                from ..analysis.static_pass import loop_summary

                if loop_summary.enabled():
                    parked = loop_summary.apply_to_states(
                        parked,
                        loop_bound=getattr(self.strategy, "bound",
                                           None))
            except Exception as e:
                log.debug("loop-summary sweep application failed: %s",
                          e)
            run = engine.last_run_stats
            if run is None:
                # packed wave: the dispatch ran on the group's shared
                # engine, not this member's — its device counters live
                # in the SolverStatistics shared bucket (wave_pack)
                run = {"device_steps": 0, "forks": 0, "records": 0,
                       "windows": 0}
            if slim_stop:
                # transaction-end shortcut: lane-retired states parked
                # at a top-level STOP skip the worklist round trip —
                # see _fast_terminal (eligibility re-checked there;
                # decliners requeue normally)
                self.work_list.extend(
                    gs for gs in parked
                    if not self._fast_terminal(gs)
                )
            else:
                self.work_list.extend(parked)
            self.total_states += run["device_steps"]
            # device-executed pcs are invisible to execute_state hooks;
            # merge the engine's visited bitmap into coverage consumers
            vis = engine.visited_by_code.get(code)
            if vis is not None and self._lane_coverage_hooks:
                env_code = states[0].environment.code
                for hook in self._lane_coverage_hooks:
                    hook(env_code.bytecode,
                         env_code.instruction_list, vis)
            log.info(
                "lane engine: %d entries -> %d parked states "
                "(%d forks, %d device steps, %d records, %d windows)",
                len(states), len(parked), run["forks"],
                run["device_steps"], run["records"], run["windows"],
            )
            trace.end("svm.sweep_retire")

    def exec(self, create=False, track_gas=False
             ) -> Optional[List[GlobalState]]:
        final_states: List[GlobalState] = []
        self._pi_wave: List[GlobalState] = []
        for hook in self._start_exec_hooks:
            hook()
        from ..support.devices import effective_tpu_lanes

        if effective_tpu_lanes() and not create and not track_gas:
            self._lane_engine_sweep()

        iter_since_sweep = 0
        # mid-round work sharding (parallel/migrate.py): poll the
        # steal-request flag every K processed states so a long-pole
        # contract sheds finished open states WHILE a round runs, not
        # only at its boundary. K comes from the bus (splittable
        # contracts poll more often).
        bus = None if create or track_gas else getattr(
            args, "migration_bus", None)
        midround_tick = 0
        # instructions this loop executed (SolverStatistics.host_steps,
        # booked once at exit), and whether the svm.host_exec span is
        # open: one span per stretch of the loop between lane sweeps,
        # whose end carries the steps of its stretch
        host_steps = stretch_from = 0
        in_host = False
        try:
            for global_state in self.strategy:
                if not in_host:
                    trace.begin("svm.host_exec")
                    in_host, stretch_from = True, host_steps
                # live-dump visibility (support/checkpoint.py): the
                # state being executed was already popped from the
                # worklist — a SIGTERM snapshot taken mid-step must
                # include it or its whole subtree is lost. Cleared
                # once its successors are safely in the worklist
                # (re-executing one step on resume is sound; issue
                # dedup absorbs it).
                self._ckpt_current_state = global_state
                if create and self._check_create_termination():
                    log.debug("Hit create timeout, returning.")
                    return final_states + [global_state] \
                        if track_gas else None
                if not create and self._check_execution_termination():
                    log.debug("Hit execution timeout, returning.")
                    return final_states + [global_state] \
                        if track_gas else None
                host_steps += 1
                try:
                    new_states, op_code = self.execute_state(global_state)
                except NotImplementedError:
                    log.debug("Encountered unimplemented instruction")
                    continue

                if (
                    self.strategy.run_check()
                    and args.pruning_factor
                    and len(new_states) > 1
                    and random.uniform(0, 1) < args.pruning_factor
                ):
                    from ..models.pruner import prune_feasible_states

                    new_states = prune_feasible_states(new_states)
                self.manage_cfg(op_code, new_states)
                # spill/refill: mid-path states that became device-
                # seedable again (host executed past their park site)
                # re-enter the lane engine periodically
                iter_since_sweep += 1
                if (
                    args.tpu_lanes
                    and not create
                    and not track_gas
                    and iter_since_sweep >= 512
                    and len(self.work_list) >= 32
                ):
                    iter_since_sweep = 0
                    trace.end("svm.host_exec",
                              steps=host_steps - stretch_from)
                    self._lane_engine_sweep(min_batch=32)
                    trace.begin("svm.host_exec")
                    stretch_from = host_steps
                if new_states:
                    self.work_list += new_states
                elif track_gas:
                    final_states.append(global_state)
                self._ckpt_current_state = None
                self.total_states += len(new_states)
                if bus is not None:
                    midround_tick += 1
                    if midround_tick >= bus.yield_every:
                        midround_tick = 0
                        bus.midround_yield(self)
                # fork-scale history also fills from HOST exploration
                # (pick_width sizes the next in-process analysis of a
                # wide-forking code). NOT gated on tpu_lanes:
                # host-only corpus runs must persist real fork peaks to
                # stats.json too (cost_model.HOST_PEAKS), or the next
                # run's pick_width/LPT warm start sees fork_peak: 0
                # (ROADMAP open item)
                if len(new_states) > 1:
                    code_obj = global_state.environment.code
                    peaks = getattr(self, "_fork_peaks", None)
                    if peaks is None:
                        # keyed by the code OBJECT, weakly: an id() key
                        # could be reused after GC and hand a new code
                        # a stale peak, while a strong key would pin
                        # every retired Disassembly for the engine's
                        # lifetime
                        import weakref

                        peaks = self._fork_peaks = \
                            weakref.WeakKeyDictionary()
                    seen, last_len = peaks.get(code_obj, (0, 0))
                    # len(work_list) only BOUNDS this code's share (a
                    # mixed-code worklist must not inflate a narrow
                    # code's scale); re-count the actual share only
                    # when the TOTAL length doubled since the last
                    # count, so a fork storm pays O(log) full walks
                    # even when another code floods the list
                    length = len(self.work_list)
                    # first multi-fork event always counts (last_len ==
                    # 0): codes whose worklist never exceeds 32 states
                    # otherwise record no fork scale at all and
                    # pick_width sees no history for them (ADVICE.md);
                    # afterwards the geometric schedule bounds re-counts
                    if last_len == 0 \
                            or length > max(2 * last_len, last_len + 32):
                        peak = sum(
                            1 for s in self.work_list
                            if s.environment.code is code_obj
                        )
                        peaks[code_obj] = (max(peak, seen), length)
                        if peak > seen:
                            self._record_fork_scale(code_obj, peak)
        finally:
            if in_host:
                trace.end("svm.host_exec", steps=host_steps - stretch_from)
            if host_steps:
                from ..smt.solver.solver_statistics import (
                    SolverStatistics,
                )

                SolverStatistics().bump(host_steps=host_steps)
            # cross-state PotentialIssue wave: every end state's
            # candidates screen in ONE interval batch (device-sized
            # where per-state discharge saw only a handful), then the
            # survivors solve as before. Runs on every exit path —
            # timeouts still discharge what was collected.
            self._discharge_pi_wave()

        for hook in self._stop_exec_hooks:
            hook()
        return final_states if track_gas else None

    def _fast_terminal(self, global_state: GlobalState) -> bool:
        """Transaction-end shortcut for a lane-retired state parked at
        a top-level STOP when no essential hook watches STOP (on either
        hook channel): replays exactly what execute_state's STOP path
        does — execute_state hooks, both pre-hook channels (lane-safe
        only, per the slim_stop gate), transaction_end hooks, the
        PotentialIssue wave append, and _add_world_state — without the
        worklist round trip, Instruction dispatch, or signal unwind
        (stop_ raises before post hooks ever fire, so none are owed).
        Returns False for ineligible states: the caller requeues them
        on the normal path. The caller guarantees the essential-hook
        check (sweep's slim_stop)."""
        from .transaction import MessageCallTransaction

        ms = global_state.mstate
        ilist = global_state.environment.code.instruction_list
        if ms.pc >= len(ilist) or ilist[ms.pc]["opcode"] != "STOP":
            return False
        tx_stack = global_state.transaction_stack
        if not tx_stack or tx_stack[-1][1] is not None:
            return False
        transaction = tx_stack[-1][0]
        if not isinstance(transaction, MessageCallTransaction):
            return False

        try:
            for hook in self._execute_state_hooks:
                hook(global_state)
        except PluginSkipState:
            return True
        try:
            self._execute_pre_hook("STOP", global_state)
        except PluginSkipState:
            return True
        for hook in self.instr_pre_hook["STOP"]:
            hook(global_state)
        ms.prev_pc = ms.pc
        # NO gas accounting or OOG check: stop_ raises the end signal
        # inside the decorated function, before StateTransition's
        # accumulate_gas/check_gas_usage_limit ever run — the real
        # STOP path always ends the transaction normally

        transaction.return_data = None
        for hook in self._transaction_end_hooks:
            hook(global_state, transaction, None, False)
        global_state.world_state.node = global_state.node
        self._pi_wave.append(global_state)
        if len(self._pi_wave) >= 256:
            self._discharge_pi_wave()
        self._add_world_state(global_state)
        return True

    @staticmethod
    def _record_fork_scale(code_obj, peak: int) -> None:
        """Feed the host worklist peak into the per-code fork-scale
        histories (best-effort): always into the cost model's host
        table (parallel/cost_model.HOST_PEAKS — what stats.json
        persists on host-only corpus runs), and into the lane engine's
        PATH_HISTORY only when the lane path is already loaded — a
        host-only run must not pay the jax/lane_engine import just to
        record a peak."""
        try:
            from ..parallel.cost_model import record_host_peak

            record_host_peak(code_obj, peak)
        except Exception:
            pass
        le = sys.modules.get("mythril_tpu.laser.lane_engine")
        if le is None:
            return
        try:
            code = le.code_to_bytes(code_obj)
            if code and peak > le.PATH_HISTORY.get(code, 0):
                le.PATH_HISTORY[code] = peak
        except Exception:
            pass

    def _discharge_pi_wave(self) -> None:
        states = getattr(self, "_pi_wave", None)
        if not states:
            return
        self._pi_wave = []
        from ..analysis.potential_issues import discharge_wave

        with trace.span("svm.pi_wave", states=len(states)):
            discharge_wave(states)

    def execute_state(
        self, global_state: GlobalState
    ) -> Tuple[List[GlobalState], Optional[str]]:
        """Execute one instruction; route VM exceptions and transaction
        signals."""
        try:
            for hook in self._execute_state_hooks:
                hook(global_state)
        except PluginSkipState:
            return [], None

        instructions = global_state.environment.code.instruction_list
        try:
            op_code = instructions[global_state.mstate.pc]["opcode"]
        except IndexError:
            self._add_world_state(global_state)
            return [], None

        if len(global_state.mstate.stack) < get_required_stack_elements(
            op_code
        ):
            error_msg = (
                "Stack Underflow Exception due to insufficient stack "
                "elements for the address {}".format(
                    instructions[global_state.mstate.pc]["address"]
                )
            )
            new_global_states = self.handle_vm_exception(
                global_state, op_code, error_msg
            )
            self._execute_post_hook(op_code, new_global_states)
            return new_global_states, op_code

        try:
            self._execute_pre_hook(op_code, global_state)
        except PluginSkipState:
            return [], None

        try:
            new_global_states = Instruction(
                op_code,
                self.dynamic_loader,
                pre_hooks=self.instr_pre_hook[op_code],
                post_hooks=self.instr_post_hook[op_code],
            ).evaluate(global_state)

        except VmException as e:
            for hook in self._transaction_end_hooks:
                hook(
                    global_state,
                    global_state.current_transaction,
                    None,
                    False,
                )
            new_global_states = self.handle_vm_exception(
                global_state, op_code, str(e)
            )

        except TransactionStartSignal as start_signal:
            new_global_state = (
                start_signal.transaction.initial_global_state()
            )
            new_global_state.transaction_stack = copy(
                global_state.transaction_stack
            ) + [(start_signal.transaction, global_state)]
            new_global_state.node = global_state.node
            new_global_state.world_state.constraints = (
                start_signal.global_state.world_state.constraints
            )
            log.debug(
                "Starting new transaction %s", start_signal.transaction
            )
            return [new_global_state], op_code

        except TransactionEndSignal as end_signal:
            (
                transaction,
                return_global_state,
            ) = end_signal.global_state.transaction_stack[-1]
            log.debug("Ending transaction %s.", transaction)

            for hook in self._transaction_end_hooks:
                hook(
                    end_signal.global_state,
                    transaction,
                    return_global_state,
                    end_signal.revert,
                )

            if return_global_state is None:
                if (
                    not isinstance(
                        transaction, ContractCreationTransaction
                    )
                    or transaction.return_data
                ) and not end_signal.revert:
                    # defer the PotentialIssue discharge to the end of
                    # this exec round: the cross-state wave screens ALL
                    # end states' candidates in one interval batch
                    # (device-sized), where per-state discharge sees
                    # only a handful at a time. Bounded: a long round
                    # discharges every 256 end states rather than
                    # retaining them all until the finally block
                    self._pi_wave.append(global_state)
                    if len(self._pi_wave) >= 256:
                        self._discharge_pi_wave()
                    end_signal.global_state.world_state.node = (
                        global_state.node
                    )
                    self._add_world_state(end_signal.global_state)
                new_global_states = []
            else:
                # execute the post hook for the tx-ending instruction
                self._execute_post_hook(
                    op_code, [end_signal.global_state]
                )
                # propagate annotations
                new_annotations = [
                    annotation
                    for annotation in global_state.annotations
                    if annotation.persist_over_calls
                ]
                return_global_state.add_annotations(new_annotations)
                new_global_states = self._end_message_call(
                    copy(return_global_state),
                    global_state,
                    revert_changes=end_signal.revert,
                    return_data=transaction.return_data,
                )

        self._execute_post_hook(op_code, new_global_states)
        return new_global_states, op_code

    def _end_message_call(
        self,
        return_global_state: GlobalState,
        global_state: GlobalState,
        revert_changes=False,
        return_data=None,
    ) -> List[GlobalState]:
        """Resume the caller frame after a sub-call completes."""
        return_global_state.world_state.constraints += (
            global_state.world_state.constraints
        )
        op_code = return_global_state.environment.code.instruction_list[
            return_global_state.mstate.pc
        ]["opcode"]

        return_global_state.last_return_data = return_data
        if not revert_changes:
            return_global_state.world_state = copy(
                global_state.world_state
            )
            return_global_state.environment.active_account = (
                global_state.accounts[
                    return_global_state.environment.active_account
                    .address.value
                ]
            )
            if isinstance(
                global_state.current_transaction,
                ContractCreationTransaction,
            ):
                return_global_state.mstate.min_gas_used += (
                    global_state.mstate.min_gas_used
                )
                return_global_state.mstate.max_gas_used += (
                    global_state.mstate.max_gas_used
                )
        try:
            new_global_states = Instruction(
                op_code,
                self.dynamic_loader,
                pre_hooks=self.instr_pre_hook[op_code],
                post_hooks=self.instr_post_hook[op_code],
            ).evaluate(return_global_state, True)
        except VmException:
            new_global_states = []

        for state in new_global_states:
            state.node = global_state.node
        return new_global_states

    def handle_vm_exception(
        self, global_state: GlobalState, op_code: str, error_msg: str
    ) -> List[GlobalState]:
        _, return_global_state = global_state.transaction_stack.pop()
        if return_global_state is None:
            # exceptional halt of a top-level tx: all changes discarded;
            # nothing new for the open-states set
            log.debug(
                "Encountered a VmException, ending path: `%s`", error_msg
            )
            new_global_states: List[GlobalState] = []
        else:
            self._execute_post_hook(op_code, [global_state])
            new_global_states = self._end_message_call(
                return_global_state,
                global_state,
                revert_changes=True,
                return_data=None,
            )
        return new_global_states

    def _add_world_state(self, global_state: GlobalState):
        """Record the world state of a finished path as an open state."""
        for hook in self._add_world_state_hooks:
            try:
                hook(global_state)
            except PluginSkipWorldState:
                return
        self._tag_last_function(global_state)
        if self._path_delay:
            time.sleep(self._path_delay)
        self.open_states.append(global_state.world_state)

    def _tag_last_function(self, global_state: GlobalState) -> None:
        """Static tx-prune context (docs/static_pass.md): remember
        WHICH function entry this finished transaction's path routed
        through, so the next round's pre-screen can consult the
        interprocedural independence relation. The tag rides the open
        world state; the round-boundary merge drops it unless every
        merged disjunct agrees (laser/merge.py)."""
        try:
            from ..analysis import static_pass

            if not static_pass.taint_enabled():
                return
            ws = global_state.world_state
            ws._mtpu_last_fentry = None
            tx = global_state.current_transaction
            from .transaction import MessageCallTransaction

            if not isinstance(tx, MessageCallTransaction):
                return
            code = global_state.environment.code
            rev = getattr(code, "_mtpu_name_to_entry", None)
            if rev is None:
                rev = {}
                for addr, fname in getattr(
                        code, "address_to_function_name", {}).items():
                    # an ambiguous name (two entries) must tag nothing
                    rev[fname] = None if fname in rev else addr
                try:
                    code._mtpu_name_to_entry = rev
                except Exception:
                    pass
            ws._mtpu_last_fentry = rev.get(
                global_state.environment.active_function_name)
        except Exception:
            pass

    # -- CFG ----------------------------------------------------------------

    @staticmethod
    def _branch_condition(state: GlobalState):
        """CFG edge label for a conditional transition: the real branch
        condition when the fork recorded one (trivially-true conditions
        are not kept in the constraint list), else the latest path
        constraint."""
        cond = getattr(state, "branch_condition", None)
        if cond is not None:
            return cond
        constraints = state.world_state.constraints
        return constraints[-1] if len(constraints) else None

    def manage_cfg(self, opcode: Optional[str],
                   new_states: List[GlobalState]) -> None:
        if opcode == "JUMP":
            assert len(new_states) <= 1
            for state in new_states:
                self._new_node_state(state)
        elif opcode == "JUMPI":
            assert len(new_states) <= 2
            for state in new_states:
                self._new_node_state(
                    state, JumpType.CONDITIONAL,
                    self._branch_condition(state),
                )
        elif opcode in ("SLOAD", "SSTORE") and len(new_states) > 1:
            for state in new_states:
                self._new_node_state(
                    state, JumpType.CONDITIONAL,
                    self._branch_condition(state),
                )
        elif opcode == "RETURN":
            for state in new_states:
                self._new_node_state(state, JumpType.RETURN)
        for state in new_states:
            if state.node:
                state.node.states.append(state)

    def _new_node_state(self, state: GlobalState,
                        edge_type=JumpType.UNCONDITIONAL,
                        condition=None) -> None:
        try:
            address = state.environment.code.instruction_list[
                state.mstate.pc
            ]["address"]
        except IndexError:
            return
        new_node = Node(state.environment.active_account.contract_name)
        old_node = state.node
        state.node = new_node
        new_node.constraints = state.world_state.constraints
        if self.requires_statespace:
            self.nodes[new_node.uid] = new_node
            # a checkpoint-restored in-flight state re-enters with its
            # node dropped (support/checkpoint.py persistent-id): its
            # subtree re-roots here without an incoming edge
            if old_node is not None:
                self.edges.append(
                    Edge(
                        old_node.uid,
                        new_node.uid,
                        edge_type=edge_type,
                        condition=condition,
                    )
                )

        if edge_type == JumpType.RETURN:
            new_node.flags |= NodeFlags.CALL_RETURN.value
        elif edge_type == JumpType.CALL:
            try:
                if "retval" in str(state.mstate.stack[-1]):
                    new_node.flags |= NodeFlags.CALL_RETURN.value
                else:
                    new_node.flags |= NodeFlags.FUNC_ENTRY.value
            except StackUnderflowException:
                new_node.flags |= NodeFlags.FUNC_ENTRY.value

        environment = state.environment
        disassembly = environment.code
        if isinstance(
            state.world_state.transaction_sequence[-1],
            ContractCreationTransaction,
        ):
            environment.active_function_name = "constructor"
        elif address in disassembly.address_to_function_name:
            environment.active_function_name = (
                disassembly.address_to_function_name[address]
            )
            new_node.flags |= NodeFlags.FUNC_ENTRY.value
            log.debug(
                "- Entering function %s:%s",
                environment.active_account.contract_name,
                new_node.function_name,
            )
        elif address == 0:
            environment.active_function_name = "fallback"

        new_node.function_name = environment.active_function_name

    # -- hook registration --------------------------------------------------

    def register_hooks(self, hook_type: str,
                       hook_dict: Dict[str, List[Callable]]):
        if hook_type == "pre":
            entrypoint = self.pre_hooks
        elif hook_type == "post":
            entrypoint = self.post_hooks
        else:
            raise ValueError(
                "Invalid hook type %s. Must be one of {pre, post}"
                % hook_type
            )
        for op_code, funcs in hook_dict.items():
            entrypoint[op_code].extend(funcs)

    def register_laser_hooks(self, hook_type: str, hook: Callable):
        if hook_type in self.hook_type_map:
            self.hook_type_map[hook_type].append(hook)
        else:
            raise ValueError(f"Invalid hook type {hook_type}")

    def register_instr_hooks(self, hook_type: str, opcode: str,
                             hook: Callable):
        if hook_type == "pre":
            if opcode is None:
                for op in OPCODES:
                    self.instr_pre_hook[op].append(hook(op))
            else:
                self.instr_pre_hook[opcode].append(hook)
        else:
            if opcode is None:
                for op in OPCODES:
                    self.instr_post_hook[op].append(hook(op))
            else:
                self.instr_post_hook[opcode].append(hook)

    def instr_hook(self, hook_type, opcode) -> Callable:
        def hook_decorator(func: Callable):
            self.register_instr_hooks(hook_type, opcode, func)

        return hook_decorator

    def laser_hook(self, hook_type: str) -> Callable:
        def hook_decorator(func: Callable):
            self.register_laser_hooks(hook_type, func)
            return func

        return hook_decorator

    def _execute_pre_hook(self, op_code: str,
                          global_state: GlobalState) -> None:
        if op_code not in self.pre_hooks.keys():
            return
        for hook in self.pre_hooks[op_code]:
            hook(global_state)

    def _execute_post_hook(self, op_code: str,
                           global_states: List[GlobalState]) -> None:
        if op_code not in self.post_hooks.keys():
            return
        for hook in self.post_hooks[op_code]:
            for global_state in global_states[:]:
                try:
                    hook(global_state)
                except PluginSkipState:
                    global_states.remove(global_state)

    def pre_hook(self, op_code: str) -> Callable:
        def hook_decorator(func: Callable):
            self.pre_hooks[op_code].append(func)
            return func

        return hook_decorator

    def post_hook(self, op_code: str) -> Callable:
        def hook_decorator(func: Callable):
            self.post_hooks[op_code].append(func)
            return func

        return hook_decorator

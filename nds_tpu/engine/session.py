# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Session: the engine's user-facing entry point (the role SparkSession plays
for the reference drivers; ref: nds/nds_power.py:204-248).

Holds the table catalog and configuration, parses and executes SQL, and
exposes collect()/write() result surfaces. DML (INSERT/DELETE for Data
Maintenance) routes through the snapshot warehouse when one is attached.
"""

from __future__ import annotations

import os
import time

import pyarrow as pa

from nds_tpu.engine.column import from_arrow
from nds_tpu.engine.table import DeviceTable
from nds_tpu.obs import trace as _obs
from nds_tpu.sql import ast as A
from nds_tpu.sql.parser import parse
from nds_tpu.sql.planner import ExecError, Planner


class Result:
    """A materialized query result."""

    def __init__(self, table: DeviceTable, qid=None):
        self.table = table
        # the statement's trace id (nds_tpu/obs): the fetch spans below run
        # after Session.sql returned, outside the statement's span, and
        # carry it so one statement stays one tree
        self.qid = qid

    @property
    def num_rows(self) -> int:
        from nds_tpu.engine import ops as E
        return E.count_int(self.table.nrows)

    @property
    def column_names(self):
        return self.table.column_names

    def to_arrow(self) -> pa.Table:
        # the device->host result fetch: the "materialize" phase of the
        # query trace (collect() and the write path both land here)
        with _obs.span("materialize", qid=self.qid):
            return self.table.to_arrow()

    def collect(self):
        """Device -> host gather; returns list of row tuples (the reference's
        df.collect() contract; ref: nds/nds_power.py:125-135)."""
        arrow = self.to_arrow()
        # host-only row building: its own span so the call is covered end
        # to end (a wide answer spends more here than in the fetch)
        with _obs.span("collect", qid=self.qid):
            cols = [arrow.column(i).to_pylist()
                    for i in range(arrow.num_columns)]
            return list(zip(*cols)) if cols else []

    def write(self, path: str, fmt: str = "parquet"):
        from nds_tpu.io.columnar import write_table
        write_table(self.to_arrow(), path, fmt)


class Session:
    def __init__(self, conf: dict | None = None):
        import os

        from nds_tpu.parallel.multihost import maybe_initialize
        maybe_initialize()       # multi-host federation precedes backend use
        from nds_tpu import enable_compile_cache
        enable_compile_cache()   # backend is resolved by session time
        self.conf = dict(conf or {})
        self.catalog: dict[str, DeviceTable] = {}
        self.base_tables: set[str] = set()   # names loaded as pristine scans
        self.warehouse = None            # attached by maintenance driver
        self.view_setup_times: list = [] # (name, ms) like setup_tables timing
        # the role Spark's applicationId plays in time logs
        # (ref: nds/nds_power.py:246,265)
        self.app_id = f"nds-tpu-{int(time.time() * 1000)}"
        self.app_name = "nds-tpu"
        # SPMD execution: with a >1 mesh (power-of-two device count; the
        # launch templates export NDS_MESH_SHAPE, base.template), base-table
        # columns are row-sharded over the mesh and GSPMD partitions every
        # engine primitive, inserting ICI collectives where Spark would
        # shuffle (SURVEY.md §2.4.1, §5.8). Bucketed physical lengths are
        # powers of two >= 16, so any such mesh divides them evenly.
        self.mesh = None
        # whole-query trace-replay compilation (engine/replay.py): keyed
        # on (query text, data version). Default ON for accelerator
        # backends (where per-dispatch launch latency and host round
        # trips add up); CPU opts in with NDS_TPU_REPLAY=force, everything off with =off.
        self._data_version = 0
        self._replay_cache: dict = {}
        self._replay_seen: set = set()
        self._replay_blacklist: set = set()
        # hybrid policy state: first-sight eager host-sync count per key,
        # consulted by 'auto' mode (see _replay_mode)
        self._replay_syncs: dict = {}
        shape = int(self.conf.get("mesh_shape") or
                    os.environ.get("NDS_MESH_SHAPE", "1"))
        if shape > 1:
            if shape & (shape - 1):
                raise ValueError(f"mesh_shape must be a power of two, "
                                 f"got {shape}")
            # every physical bucket must divide evenly across the mesh; the
            # floor is a process-wide shape contract, so it is configured by
            # environment (NDS_TPU_MIN_BUCKET) at import, never mutated here
            from nds_tpu.engine import ops as _ops
            if shape > _ops._MIN_BUCKET:
                raise ValueError(
                    f"mesh_shape {shape} exceeds the physical bucket floor "
                    f"{_ops._MIN_BUCKET}; start the process with "
                    f"NDS_TPU_MIN_BUCKET={shape} (or larger power of two)")
            import jax
            n_avail = len(jax.devices())
            if n_avail < shape:
                raise ValueError(
                    f"mesh_shape {shape} exceeds the {n_avail} available "
                    f"device(s); silent truncation would under-shard")
            from nds_tpu.parallel import make_mesh
            self.mesh = make_mesh(shape)

    # -- catalog ------------------------------------------------------------

    def _shard_table(self, table: DeviceTable) -> DeviceTable:
        """Place a table over the session mesh (no-op without one).

        The broadcast-vs-shard decision is made here, at load time: tables
        under the broadcast byte threshold are REPLICATED (every device
        holds the whole table, so joins against them are local probes — the
        all-gather-join side of the planner's broadcast/repartition choice,
        Spark's autoBroadcastJoinThreshold analog); larger tables are
        row-sharded, and big x big joins repartition through the ICI
        all-to-all exchange (engine/ops.py join path, parallel/exchange.py).
        Ref: SURVEY.md §5.8, nds/power_run_cpu.template:30."""
        if self.mesh is None:
            return table
        import os

        import jax
        from dataclasses import replace as _replace
        from jax.sharding import NamedSharding, PartitionSpec as P
        limit = int(self.conf.get(
            "broadcast_bytes",
            os.environ.get("NDS_TPU_BROADCAST_BYTES", str(128 << 20))))
        approx = sum(c.data.nbytes +
                     (c.valid.nbytes if c.valid is not None else 0)
                     for c in table.columns.values())
        spec = P() if approx <= limit else P("part")
        sh = NamedSharding(self.mesh, spec)
        cols = {}
        for n, c in table.columns.items():
            cols[n] = _replace(
                c, data=jax.device_put(c.data, sh),
                valid=None if c.valid is None else jax.device_put(c.valid, sh))
        return DeviceTable(cols, table.nrows, plen=table.plen)

    def create_temp_view(self, name: str, table, base: bool = False,
                         arrow=None) -> None:
        """Register a table. ``base=True`` marks a pristine base-table load
        (raw/columnar/warehouse readers), which lets the planner trust
        schema facts like primary-key uniqueness; any re-registration under
        the same name through a non-base path revokes the marker.
        ``arrow`` optionally passes the host-side source table so load-time
        statistics can be collected without any device->host read."""
        from nds_tpu.engine.table import ChunkedTable
        if isinstance(table, pa.Table):
            arrow = table if arrow is None else arrow
            table = from_arrow(table)
        key = name.lower()
        if isinstance(table, ChunkedTable):
            self.catalog[key] = table        # host-resident; never sharded
        else:
            self.catalog[key] = self._shard_table(table)
        if base and arrow is not None:
            self._collect_load_stats(key, arrow)
        if base:
            self.base_tables.add(key)
        else:
            self.base_tables.discard(key)
        # invalidate compiled replays: keys embed the version, so nothing
        # compiled before this mutation can ever hit again — clear all
        # three (the blacklist re-derives per data version)
        self._data_version += 1
        self._replay_cache.clear()
        self._replay_seen.clear()
        self._replay_blacklist.clear()

    def _collect_load_stats(self, key: str, arrow) -> None:
        """Load-time key statistics from HOST data (DESIGN.md item 2: one
        scan at load instead of a device->host sync at query time).

        Today this prewarms the dense-dimension position map for a table
        whose FIRST column is a unique dense integer key (every TPC-DS
        dimension PK is; ref: nds/nds_schema.py surrogate keys), so the
        first star join against it needs no whole-column device fetch."""
        import numpy as np
        t = self.catalog.get(key)
        if self.mesh is not None or not isinstance(t, DeviceTable) or \
                not t.columns:
            return
        first = next(iter(t.columns))
        col = t.columns[first]
        n = t.nrows if isinstance(t.nrows, int) else None
        if not n or n > (1 << 24) or col.kind == "str" or \
                first not in arrow.column_names:
            return
        src = arrow.column(first)
        if src.null_count or not pa.types.is_integer(src.type):
            return
        live = src.to_numpy(zero_copy_only=False).astype(np.int64)
        if len(live) != n:
            return
        mn = int(live.min())
        span = int(live.max()) - mn + 1
        # the same density gate _dense_dim_info applies at query time
        if span > max(4 * n, 1 << 16) or span > (1 << 26):
            return
        pos = np.full(span, n, dtype=np.int64)
        pos[live - mn] = np.arange(n)
        if int((pos != n).sum()) != n:
            return                            # duplicate keys: not a PK
        from nds_tpu.engine import ops as E
        import jax.numpy as jnp
        E._identity_cache(E._dense_dim_cache, 64, (col.data,),
                          lambda: (mn, jnp.asarray(pos)), static_key=n)

    def read_raw_view(self, name: str, path: str, fields) -> float:
        """Register a raw '|'-delimited table; returns elapsed seconds (the
        per-view creation timing in the reference's setup_tables;
        ref: nds/nds_power.py:79-106)."""
        from nds_tpu.io import read_raw_table
        start = time.perf_counter()
        arrow = read_raw_table(path, fields)
        canonical = {f.name: f.type for f in fields}
        self.create_temp_view(name, from_arrow(arrow, canonical), base=True,
                              arrow=arrow)
        return time.perf_counter() - start

    def read_columnar_view(self, name: str, path: str, fmt: str = "parquet",
                           canonical_types: dict | None = None) -> float:
        import os

        from nds_tpu.engine.table import ChunkedTable
        from nds_tpu.io import read_table
        start = time.perf_counter()
        arrow = read_table(path, fmt)
        # >HBM streaming decision: a table past the stream threshold stays
        # host-resident and is bound chunk-by-chunk by the planner (the
        # role of Spark's file splits; SURVEY.md §5.7). A meshed session
        # row-shards instead — the mesh multiplies device capacity.
        # float() first: operators write thresholds like "1.5e9"
        limit = int(float(self.conf.get(
            "stream_bytes",
            os.environ.get("NDS_TPU_STREAM_BYTES", str(8 << 30)))))
        if self.mesh is None and arrow.nbytes > limit:
            self.create_temp_view(
                name, ChunkedTable(arrow, canonical_types), base=True)
        else:
            self.create_temp_view(name, from_arrow(arrow, canonical_types),
                                  base=True, arrow=arrow)
        return time.perf_counter() - start

    # -- SQL ----------------------------------------------------------------

    def _replay_mode(self) -> str:
        """Replay policy: 'off' | 'auto' | 'on' | 'force'.

        Replayed queries floor at ~1 host round trip, and for LOW-sync
        queries the pipelined eager stream can be faster end to end —
        but every eager host sync flushes the dispatch queue, so
        HIGH-sync queries (q14 16 syncs, q28/q77 12) pay that many
        times. What one sync costs on a local chip is not measured yet.
        The default 'auto' is the hybrid: a query records+replays
        only when its first-sight eager run counted more host syncs than
        NDS_TPU_REPLAY_SYNC_THR (default 6 — the reference pays one round
        trip per query, ref nds/nds_power.py:125-135); everything else
        stays eager. 'on'/'force' replay unconditionally, 'off'
        disables.
        """
        default = self.conf.get("replay")
        if default is None:
            # accelerator backends default to the hybrid: every eager host
            # sync pays the dispatch-path round trip there. CPU (the test
            # platform) stays off — XLA:CPU megaprogram compile sequences
            # are flaky on small hosts and tests opt in explicitly.
            import jax
            default = "off" if jax.default_backend() == "cpu" else "auto"
        env = os.environ.get("NDS_TPU_REPLAY", str(default))
        env = env.lower()
        if env in ("on", "1", "true"):
            return "on"
        if env == "force":
            return "force"
        if env == "auto":
            return "auto"
        return "off"

    def _replay_on(self) -> bool:
        return self._replay_mode() != "off"

    def _sync_threshold(self) -> int:
        return int(os.environ.get(
            "NDS_TPU_REPLAY_SYNC_THR",
            str(self.conf.get("replay_sync_threshold", 6))))

    def _replay_wanted(self, key) -> bool:
        """Should the 2nd sight of ``key`` record+compile a replay?"""
        mode = self._replay_mode()
        if mode in ("on", "force"):
            return True
        return self._replay_syncs.get(key, 0) > self._sync_threshold()

    def replay_pending(self, text: str) -> bool:
        """True if the next sql(text) would record or trace a replay
        program (drivers use this to fold the record/trace passes into
        warmup so timed passes measure steady state)."""
        key = (text, self._data_version)
        if self._replay_mode() == "off" or key in self._replay_blacklist:
            return False
        if key in self._replay_cache:
            hit = self._replay_cache[key]
            return bool(hit.first_run)
        return key in self._replay_seen and self._replay_wanted(key)

    def _sql_replay(self, text: str, stmt, planner) -> Result:
        """Trace-replay execution tiers (engine/replay.py): 1st sight of a
        query runs eagerly; 2nd records host decisions and compiles the
        whole pipeline into one XLA program; 3rd+ is one dispatch."""
        from nds_tpu.engine import ops as E
        from nds_tpu.engine import replay as R
        import time as _time
        key = (text, self._data_version)
        hit = self._replay_cache.get(key)
        if hit is not None:
            try:
                t0 = _time.perf_counter()
                out = hit.run(block=True)
                replay_s = _time.perf_counter() - t0
                # SELF-TUNING: a giant fused program is not always faster
                # than the pipelined eager stream. Compare against the recorded eager
                # wall (both sides block-to-completion); two consecutive
                # slower runs evict the program and the query stays eager
                # for this data version. The FIRST hit pays the one-time
                # XLA compile and is excluded from strike accounting.
                if hit.first_run:
                    hit.first_run = False
                elif replay_s > hit.eager_s * 1.1:
                    hit.strikes += 1
                    if hit.strikes >= 2:
                        self._replay_cache.pop(key, None)
                        self._replay_blacklist.add(key)
                else:
                    hit.strikes = 0
                self.last_scanned = dict(hit.scan_bytes)
                return Result(out)
            except E.ReplayMismatch:
                # structural divergence: permanently unreplayable
                self._replay_cache.pop(key, None)
                self._replay_blacklist.add(key)
            except Exception as exc:
                # transient runtime failure (device preemption, transfer
                # error): surface it, keep the compiled program, fall back
                # eager for THIS execution only
                from nds_tpu.listener import report_task_failure
                report_task_failure(
                    "replayed query dispatch (one-off eager fallback)", exc)
        if key in self._replay_seen and key not in self._replay_blacklist \
                and key not in self._replay_cache \
                and self._replay_wanted(key):
            if not R.record_eligible(self, stmt):
                # binds a >HBM chunked scan: whole-query record/replay
                # never applies — its streaming is compiled one layer down
                # by the chunk pipeline (engine/stream.py, via
                # _stream_join_parts). Blacklisting stops replay_pending()
                # from advertising a record pass that will never happen.
                self._replay_blacklist.add(key)
            else:
                E.resolve_counts()   # stray pending counts must not enter
                t0 = _time.perf_counter()
                with _obs.span("replay.record"):
                    with E.recording() as log:
                        table = planner.query(stmt)
                # block to completion so eager_s is a true wall, comparable
                # to the blocked replay wall (async dispatch would
                # otherwise under-count the eager side and mis-tune the
                # eviction)
                import jax as _jax
                if table.columns:
                    _jax.block_until_ready(
                        next(iter(table.columns.values())).data)
                eager_s = _time.perf_counter() - t0
                # deferred SQL runtime checks from the record pass must
                # raise NOW: inside compile() they would be swallowed by
                # the blacklist handler below and the error lost for good
                E.flush_deferred_checks()
                try:
                    cq = R.CompiledQuery(self, stmt, log,
                                         R.out_template_of(table)).compile()
                    cq.scan_bytes = dict(planner.scanned)
                    cq.eager_s = eager_s
                    cq.strikes = 0
                    cq.first_run = True
                    self._replay_cache[key] = cq
                except Exception:
                    self._replay_blacklist.add(key)
                return Result(table)
        self._replay_seen.add(key)
        # first sight: count this query's eager host syncs — the signal
        # 'auto' mode gates recording on (fetch-time syncs land after the
        # return and are not counted; the threshold is calibrated for that)
        s0 = E.sync_count()
        out = Result(planner.query(stmt))
        self._replay_syncs[key] = E.sync_count() - s0
        return out

    def sql(self, text: str) -> Result:
        # scope this thread's trace ring (mirrors the thread-scoped
        # listener): a query-executing thread drains only its own spans
        _obs.attach()
        # the root of the statement's span tree: parse, dispatch and the
        # deferred checks all sit under it, and it draws the statement id
        # (qid) every span below inherits and the Result keeps
        with _obs.span(_obs.STATEMENT) as root:
            out = self._sql_statement(text)
        out.qid = root.qid
        return out

    def _sql_statement(self, text: str) -> Result:
        with _obs.span("parse"):
            stmt = parse(text)
        planner = Planner(self.catalog, base_tables=self.base_tables)
        # roofline accounting: bytes of every catalog table the statement
        # binds (read by the Power Run's per-query summaries)
        self.last_scanned = planner.scanned
        from nds_tpu.engine import ops as E
        # statement-end barrier around EVERY dispatch path (not just
        # A.Query): CREATE TEMP VIEW ... AS SELECT, INSERT ... SELECT and
        # DELETE all run planner.query() and can register lazy
        # scalar-subquery checks; without the barrier those leak and raise
        # inside a later statement's first resolution (misattributed), and
        # a failed statement's half-registered checks mask its real error
        # per-statement watchdog scope (engine/faults.py): with
        # NDS_TPU_STATEMENT_DEADLINE_S armed, every blocking wait below
        # charges ONE shared statement budget — a hung sync or stuck
        # peer raises a classified StatementTimeout (drivers mark the
        # statement `timeout`) instead of hanging the process. Unset:
        # zero overhead.
        from nds_tpu.engine import faults as _F
        try:
            with _F.statement_scope():
                out = self._sql_dispatch(text, stmt, planner)
        except BaseException:
            E.discard_deferred_checks()
            raise
        E.flush_deferred_checks()
        return out

    def _sql_dispatch(self, text: str, stmt, planner) -> Result:
        if isinstance(stmt, A.Query):
            if self._replay_on():
                return self._sql_replay(text, stmt, planner)
            return Result(planner.query(stmt))
        if isinstance(stmt, A.CreateTempView):
            # route through create_temp_view so a meshed session re-shards
            # the view like every other catalog entry
            self.create_temp_view(stmt.name, planner.query(stmt.query))
            return Result(DeviceTable({}, 0))
        if isinstance(stmt, A.InsertInto):
            if self.warehouse is None:
                raise ExecError("INSERT requires an attached warehouse")
            rows = planner.query(stmt.query)
            self.warehouse.insert(stmt.table, rows.to_arrow())
            # route through create_temp_view so a meshed session re-shards
            # the refreshed table like every other catalog entry
            self.create_temp_view(stmt.table,
                                  from_arrow(self.warehouse.read(stmt.table)))
            return Result(DeviceTable({}, 0))
        if isinstance(stmt, A.DeleteFrom):
            if self.warehouse is None:
                raise ExecError("DELETE requires an attached warehouse")
            # evaluate the predicate against the current table; delete by mask
            import jax.numpy as jnp
            from nds_tpu.engine import ops as E
            table = self.catalog[stmt.table.lower()]
            aliased = planner._alias_table(table, stmt.table)
            if stmt.where is None:
                keep_mask = jnp.zeros(table.plen, dtype=bool)
            else:
                mask = planner._conjunct_mask(aliased,
                                              planner._split_conjuncts(stmt.where))
                keep_mask = ~mask
            # maintenance boundary: shrink eagerly — the kept table is
            # re-registered and written back, so tight buckets pay off
            kept = E.compact_table(table, keep_mask, shrink=True)
            self.warehouse.overwrite(stmt.table, kept.to_arrow())
            self.create_temp_view(stmt.table, kept)
            return Result(DeviceTable({}, 0))
        raise ExecError(f"unsupported statement {type(stmt).__name__}")

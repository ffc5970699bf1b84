# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""DeviceTable: an ordered set of named device columns of equal length.

Padded-prefix invariant: columns may be physically longer than the table's
logical row count (``nrows``); rows past ``nrows`` are garbage pads that
every operator ignores (see :mod:`nds_tpu.engine.ops` — bucketed shapes).
``plen`` is the physical length.
"""

from __future__ import annotations

from dataclasses import dataclass

from nds_tpu.engine.column import Column


@dataclass(eq=False)
class DeferredGroup:
    """The columns a join brings to a table, not gathered yet: row
    ``index[i]`` of ``source`` belongs to the table's row ``i`` (clip
    mode, as every row gather), NULL where ``match[i]`` is false (a LEFT
    join's null-extension; None where the misses are the planner's
    deferred mask). A PK-gather join leaves the dimension's columns as one
    group on the fact; a hash join's pair table (``ops.pair_table``) is
    two groups, one a side, and nothing else. ``ops.gather_table_rows``
    gathers the source through ``take(index, idx)``, so a column first
    exists at the width of whatever reads it: the residual that names it,
    or the compaction or join that consumes the table. ``pair`` marks a
    side of a pair table: no join gathered its columns at any width, so
    every array read through ``index`` counts as a deferred one
    (``op.gather``'s ``deferredArrays``)."""

    source: "DeviceTable"
    index: object                      # int array at the table's width
    match: object = None               # bool array at the table's width
    pair: bool = False


@dataclass(eq=False)
class _Deferred:
    """A table's entry for column ``name`` of ``group.source``."""

    group: DeferredGroup
    name: str


class DeviceTable:
    def __init__(self, columns: dict, nrows: int | None = None,
                 plen: int | None = None):
        # name -> Column, or _Deferred until something reads the column
        self._cols = dict(columns)
        if nrows is None:
            nrows = self._first_len(0)
        self.nrows = nrows
        # physical length; only meaningful to pass for column-less tables
        # (aggregation contexts carry capacity without materialized columns)
        self._plen = self._first_len(nrows) if plen is None else plen

    def _first_len(self, default: int) -> int:
        for e in self._cols.values():
            return int(e.group.index.shape[0]) if isinstance(e, _Deferred) \
                else len(e)
        return default

    @property
    def plen(self) -> int:
        return self._first_len(self._plen)

    @property
    def columns(self) -> dict[str, Column]:
        """Every column, gathered (see :meth:`materialize`). Read one
        column with ``table[name]``, and names or kinds with
        ``column_names`` / ``kind``, to leave the rest deferred."""
        return self.materialize()._cols

    def materialize(self) -> "DeviceTable":
        """Gather every deferred column at the table's own width, one
        fused gather a group (what the join that deferred them would have
        gathered at once), and keep them; returns the table."""
        from nds_tpu.engine.ops import gather_deferred
        for group, src in self.split()[1]:
            got = gather_deferred(group, list(src.values()), self.nrows)
            for n, s in src.items():
                self._cols[n] = got[s]
        return self

    @property
    def column_names(self):
        return list(self._cols.keys())

    def __getitem__(self, name: str) -> Column:
        if isinstance(self._cols[name], _Deferred):
            # a deferred column read alone: that one is gathered and kept
            self._cols[name] = self.select([name]).materialize()._cols[name]
        return self._cols[name]

    def __contains__(self, name: str) -> bool:
        return name in self._cols

    def kind(self, name: str) -> str:
        """A column's device kind, from the source's metadata where the
        column is deferred (no gather)."""
        e = self._cols[name]
        return e.group.source.kind(e.name) if isinstance(e, _Deferred) \
            else e.kind

    def split(self):
        """``(gathered, groups)``: the columns that exist, by name, and per
        deferred group ``(group, {name: source column})``."""
        gathered, groups = {}, {}
        for n, e in self._cols.items():
            if isinstance(e, _Deferred):
                groups.setdefault(id(e.group), (e.group, {}))[1][n] = e.name
            else:
                gathered[n] = e
        return gathered, list(groups.values())

    def with_deferred(self, source: "DeviceTable", index, match=None,
                      pair: bool = False) -> "DeviceTable":
        """This table with every column of ``source`` joined on as one
        deferred group (``index`` and ``match`` at this table's width)."""
        group = DeferredGroup(source, index, match, pair)
        cols = dict(self._cols)
        cols.update({n: _Deferred(group, n) for n in source.column_names})
        return DeviceTable(cols, self.nrows, self.plen)

    def select(self, names) -> "DeviceTable":
        return DeviceTable({n: self._cols[n] for n in names}, self.nrows,
                           self.plen)

    def with_column(self, name: str, col: Column) -> "DeviceTable":
        cols = dict(self._cols)
        cols[name] = col
        return DeviceTable(cols, self.nrows, self.plen)

    def rename(self, mapping: dict[str, str]) -> "DeviceTable":
        return DeviceTable(
            {mapping.get(n, n): c for n, c in self._cols.items()},
            self.nrows, self.plen)

    def take(self, indices, nrows: int | None = None) -> "DeviceTable":
        """Row gather (one fused device dispatch for every column): logical
        length defaults to the index count (exact materialization). Pass
        ``nrows`` when gathering with a padded index vector or permutation
        to preserve the logical count."""
        from nds_tpu.engine.ops import gather_table_rows
        n = int(indices.shape[0]) if nrows is None else nrows
        if not self._cols:
            return DeviceTable({}, n, plen=int(indices.shape[0]))
        return gather_table_rows(self, indices, n)

    def to_arrow(self):
        from nds_tpu.engine.column import to_arrow
        return to_arrow(self)

    @staticmethod
    def from_arrow(table, canonical_types=None) -> "DeviceTable":
        from nds_tpu.engine.column import from_arrow
        return from_arrow(table, canonical_types)

    def __repr__(self):
        cols = ", ".join(f"{n}:{self.kind(n)}" for n in self._cols)
        return f"DeviceTable[{self.nrows}/{self.plen} rows]({cols})"


class ChunkedTable:
    """A host-resident (arrow) table streamed through queries in row
    chunks — the scan path for tables larger than device HBM (SURVEY.md
    §5.7: "operators must stream/partition tables larger than HBM", the
    structural place sequence parallelism occupies in a model framework;
    the reference's analog is Spark file splits +
    spark.sql.files.maxPartitionBytes, ref: nds/power_run_gpu.template:30).

    The planner binds each device chunk in turn and runs the normal join
    graph per chunk (filters and joins shrink the chunk before anything is
    kept), concatenating the surviving rows; aggregation runs downstream on
    the union, so no operator ever sees the whole table on device. Chunk
    row counts are a fixed power of two, so every full chunk reuses the
    same XLA executables.
    """

    def __init__(self, arrow, canonical_types: dict | None = None,
                 chunk_rows: int | None = None):
        import os
        self.arrow = arrow
        self.canonical_types = canonical_types or {}
        self.chunk_rows = int(chunk_rows or os.environ.get(
            "NDS_TPU_STREAM_CHUNK_ROWS", str(1 << 22)))
        # unified per-column string encodings for the compiled streaming
        # executor (built lazily by padded_chunks; shared across select()
        # views, since a projection never changes column contents)
        self._str_store: dict = {}
        # per-column narrow codecs (io/columnar.plan_column_codec): whole-
        # table FOR/dict encodings the padded chunks slice — same shared-
        # store discipline as the string dictionaries. None marks a column
        # already found unencodable, so the stats pass runs once.
        self._enc_store: dict = {}
        # persistent wire plans (io/chunk_store.py, NDS_TPU_CHUNK_STORE):
        # one whole-table pre-encoded plan per column set, loaded (mmap)
        # or built+saved once — shared across select() views like the
        # codec stores above. Keyed by column-name tuple so a pruned
        # view's plan never serves the full table's.
        self._wire_store: dict = {}

    @property
    def nrows(self) -> int:
        return self.arrow.num_rows

    @property
    def nbytes(self) -> int:
        return self.arrow.nbytes

    @property
    def column_names(self):
        return list(self.arrow.column_names)

    def select(self, names) -> "ChunkedTable":
        out = ChunkedTable(self.arrow.select(names), self.canonical_types,
                           self.chunk_rows)
        out._str_store = self._str_store
        out._enc_store = self._enc_store
        out._wire_store = self._wire_store
        return out

    def device_chunks(self):
        """Yield DeviceTable chunks (at least one, possibly empty, so the
        schema always survives to the consumer)."""
        from nds_tpu.engine.column import from_arrow
        n = self.arrow.num_rows
        if n == 0:
            yield from_arrow(self.arrow, self.canonical_types)
            return
        for s in range(0, n, self.chunk_rows):
            sl = self.arrow.slice(s, min(self.chunk_rows, n - s))
            yield from_arrow(sl.combine_chunks(), self.canonical_types)

    @property
    def chunk_cap(self) -> int:
        """Uniform physical capacity of every padded chunk."""
        from nds_tpu.engine.ops import bucket_len
        return bucket_len(self.chunk_rows)

    def num_chunks(self) -> int:
        n = self.arrow.num_rows
        return max(1, -(-n // self.chunk_rows))

    def _string_encodings(self) -> dict:
        """name -> (int32 codes, shared value table, valid | None) for every
        string column, encoded ONCE against a single whole-table dictionary.

        The compiled streaming executor runs one traced program over every
        chunk; dictionary codes are device DATA in that program while the
        value table is host metadata baked into the trace, so all chunks
        must share one dictionary (per-chunk encodings would make the same
        code mean different strings chunk to chunk). The value table is
        also handed out as the SAME host object for every chunk, keeping
        identity-keyed caches (rank maps, expression fusion) warm. Cached
        per column in a store shared with select() views."""
        import numpy as np
        import pyarrow as pa
        import pyarrow.compute as pc
        from nds_tpu import types as _t
        enc: dict = {}
        for name in self.arrow.column_names:
            hit = self._str_store.get(name)
            if hit is not None:
                enc[name] = hit
                continue
            ct = self.canonical_types.get(name) or _t.arrow_to_canonical(
                self.arrow.schema.field(name).type)
            if _t.device_kind(ct) != "str":
                continue
            col = self.arrow[name].combine_chunks()
            if not pa.types.is_dictionary(col.type):
                col = pc.dictionary_encode(col)
            codes = np.asarray(
                pc.fill_null(col.indices, 0).to_numpy(zero_copy_only=False),
                dtype=np.int32)
            values = np.asarray(col.dictionary.to_pylist(), dtype=object)
            if values.size == 0:
                values = np.asarray([""], dtype=object)
            valid = None
            if col.null_count:
                valid = ~np.asarray(pc.is_null(col).to_numpy(
                    zero_copy_only=False))
            enc[name] = self._str_store[name] = (codes, values, valid)
        return enc

    def _int_encodings(self) -> dict:
        """name -> (narrow whole-table codes, valid | None, Encoding) for
        every encodable int-path column (io/columnar.plan_column_codec),
        computed ONCE per table and shared across select() views — the
        same chunk-invariance discipline as the string dictionaries, so
        the compiled streaming executor's single traced program serves
        every chunk and the Encoding objects are cache-key members.
        Empty when NDS_TPU_ENCODED=0 (the escape hatch; read per call,
        the computed plan stays cached for a later re-enable)."""
        from nds_tpu.io.columnar import encoded_enabled, plan_column_codec
        if not encoded_enabled():
            return {}
        from nds_tpu import types as _t
        out = {}
        for name in self.arrow.column_names:
            if name not in self._enc_store:
                ct = self.canonical_types.get(name) or _t.arrow_to_canonical(
                    self.arrow.schema.field(name).type)
                self._enc_store[name] = plan_column_codec(self.arrow[name],
                                                          ct)
            got = self._enc_store[name]
            if got is not None:
                out[name] = got
        return out

    def _wire_plan(self):
        """``name -> io.chunk_store.WireColumn`` when the persistent
        chunk store is active (``NDS_TPU_CHUNK_STORE``): the whole-table
        pre-encoded wire arrays ``padded_chunks`` slices per chunk. A
        warm store entry memory-maps straight back (no arrow slicing, no
        codec planning); a miss or a stale fingerprint builds the plan
        from the live codecs and persists it. None when the store is off
        — ``padded_chunks`` then keeps the inline arrow path, bit for
        bit."""
        from nds_tpu.io import chunk_store
        from nds_tpu.io.columnar import encoded_enabled
        root = chunk_store.store_root()
        if root is None:
            return None
        # keyed by column set AND the encoded gate: a post-build
        # NDS_TPU_ENCODED flip must rebuild (the on-disk entry's
        # fingerprint covers the same flag, so disk stays honest too)
        key = (tuple(self.arrow.column_names), encoded_enabled())
        hit = self._wire_store.get(key)
        if hit is not None:
            return hit
        from nds_tpu.engine import faults as _F
        try:
            plan = chunk_store.load_plan(root, self.arrow,
                                         self.canonical_types)
        except (chunk_store.ChunkStoreCorrupt, _F.FaultInjected) as exc:
            # chunk-store-read seam recovery (transient, bounded at one
            # re-encode): the store is a CACHE of the source arrow data,
            # so a corrupt entry (torn write, bit rot, injected fault)
            # is deleted and rebuilt from source — evidence-recorded,
            # never a failed statement, never corrupt codes uploaded.
            # Version drift stays a loud ChunkStoreError (fatal).
            _F.record_fault_event("chunk-store-read", "recovered",
                                  attempt=1, detail=str(exc)[:200])
            chunk_store.invalidate_entry(root, self.arrow,
                                         self.canonical_types)
            plan = None
        if plan is None:
            plan = self._build_wire_plan()
            # persisting is best-effort: a full disk, a read-only store
            # or a concurrent writer's rename race must degrade to the
            # in-memory plan just built, never fail the statement (a
            # LOAD problem — version drift, checksum — stays loud)
            try:
                chunk_store.save_plan(root, self.arrow,
                                      self.canonical_types, plan)
            except Exception as exc:
                # chunk-store-write seam degrade (evidence-recorded):
                # the statement proceeds on the plan just built
                _F.record_fault_event("chunk-store-write", "degrade",
                                      detail=str(exc)[:200])
                import logging
                logging.getLogger(__name__).warning(
                    "chunk store save failed (%s); serving the "
                    "in-memory wire plan for this process", exc)
        self._wire_store[key] = plan
        return plan

    def _build_wire_plan(self) -> dict:
        """The wire form of every column, from the live whole-table
        codecs: string dictionaries, narrow FOR/dict codes, and a host
        lowering of the remaining plain columns — exactly the arrays the
        inline ``padded_chunks`` path derives, assembled once so the
        chunk store can persist them."""
        from nds_tpu import types as _t
        from nds_tpu.io.chunk_store import WireColumn, lower_plain_column
        strings = self._string_encodings()
        narrow = self._int_encodings()
        plan = {}
        for name in self.arrow.column_names:
            ct = self.canonical_types.get(name) or _t.arrow_to_canonical(
                self.arrow.schema.field(name).type)
            if name in strings:
                codes, values, valid = strings[name]
                plan[name] = WireColumn("str", codes, valid, values,
                                        None, "str")
            elif name in narrow:
                codes, valid, enc = narrow[name]
                plan[name] = WireColumn("enc", codes, valid, None, enc,
                                        _t.device_kind(ct))
            else:
                data, valid = lower_plain_column(self.arrow[name], ct)
                plan[name] = WireColumn("plain", data, valid, None, None,
                                        _t.device_kind(ct))
        return plan

    def padded_chunks(self):
        """Yield DeviceTable chunks at ONE uniform physical capacity
        (``chunk_cap``), the final partial chunk zero-padded up to it, with
        every column carrying an explicit validity mask (False past the
        live prefix). Chunk k then differs from chunk j only in buffer
        CONTENTS — same shapes, same pytree structure, same dictionaries —
        which is what lets the compiled streaming executor drive every
        chunk through a single traced program (engine/stream.py).

        With the persistent chunk store active (``NDS_TPU_CHUNK_STORE``)
        the chunks slice pre-encoded whole-table wire arrays — possibly
        memory-mapped from a previous run — instead of slicing arrow and
        re-planning codecs; the store path produces bit-identical
        buffers (same codecs, same lowering math)."""
        import jax.numpy as jnp
        import numpy as np
        from nds_tpu import types as _t
        from nds_tpu.engine.column import Column, from_arrow_array
        cap = self.chunk_cap
        n = self.arrow.num_rows
        wire = self._wire_plan()
        if wire is not None:
            yield from self._padded_chunks_wire(wire, cap, n)
            return
        strings = self._string_encodings()
        narrow = self._int_encodings()
        for s in (range(0, n, self.chunk_rows) if n else (0,)):
            live = min(self.chunk_rows, n - s) if n else 0
            live_np = np.arange(cap) < live
            sl = self.arrow.slice(s, live)
            cols = {}
            for name in self.arrow.column_names:
                if name in strings:
                    codes, values, valid = strings[name]
                    data = np.zeros(cap, dtype=np.int32)
                    data[:live] = codes[s:s + live]
                    v = live_np if valid is None else \
                        live_np & np.concatenate(
                            [valid[s:s + live],
                             np.zeros(cap - live, dtype=bool)])
                    cols[name] = Column("str", jnp.asarray(data),
                                        jnp.asarray(v), values)
                    continue
                ct = self.canonical_types.get(name) or _t.arrow_to_canonical(
                    self.arrow.schema.field(name).type)
                if name in narrow:
                    # encoded upload: slice the whole-table narrow codes
                    # (host->device moves 2/4 B per row instead of 4/8)
                    codes, valid, enc = narrow[name]
                    data = np.zeros(cap, dtype=codes.dtype)
                    data[:live] = codes[s:s + live]
                    v = live_np if valid is None else \
                        live_np & np.concatenate(
                            [valid[s:s + live],
                             np.zeros(cap - live, dtype=bool)])
                    cols[name] = Column(_t.device_kind(ct),
                                        jnp.asarray(data),
                                        jnp.asarray(v), None, enc)
                    continue
                c = from_arrow_array(sl[name], ct, cap)
                # canonical validity structure: a chunk without nulls must
                # present the same pytree as a sibling with them, or every
                # null-pattern change would retrace the compiled program
                v = jnp.asarray(live_np) if c.valid is None else \
                    c.valid & jnp.asarray(live_np)
                cols[name] = Column(c.kind, c.data, v, c.dict_values)
            yield DeviceTable(cols, live, plen=cap)

    def _padded_chunks_wire(self, wire: dict, cap: int, n: int):
        """The store-backed twin of the inline ``padded_chunks`` body:
        slice every column's whole-table wire array (codes / lowered
        values, possibly mmapped) into zero-padded chunk buffers. Same
        shapes, same dictionaries, same validity structure — a pipeline
        compiled against either path serves the other."""
        import jax.numpy as jnp
        import numpy as np
        from nds_tpu.engine.column import Column
        for s in (range(0, n, self.chunk_rows) if n else (0,)):
            live = min(self.chunk_rows, n - s) if n else 0
            live_np = np.arange(cap) < live
            cols = {}
            for name in self.arrow.column_names:
                wc = wire[name]
                data = np.zeros(cap, dtype=wc.data.dtype)
                data[:live] = wc.data[s:s + live]
                v = live_np if wc.valid is None else \
                    live_np & np.concatenate(
                        [wc.valid[s:s + live],
                         np.zeros(cap - live, dtype=bool)])
                cols[name] = Column(wc.kind, jnp.asarray(data),
                                    jnp.asarray(v), wc.values, wc.enc)
            yield DeviceTable(cols, live, plen=cap)

    def materialize(self) -> DeviceTable:
        from nds_tpu.engine.column import from_arrow
        return from_arrow(self.arrow, self.canonical_types)

    def __repr__(self):
        return (f"ChunkedTable[{self.nrows} rows x "
                f"{len(self.arrow.column_names)} cols, "
                f"chunk={self.chunk_rows}]")

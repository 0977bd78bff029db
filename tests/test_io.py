"""Data-plane tests, modeled on the reference's TestReader
(tony-core/src/test/.../TestReader.java:41-80): exhaustive split-coverage
property check plus multi-file, multi-reader exactly-once reads on the
local filesystem."""

import json

import numpy as np
import pytest

from tony_tpu.io import (
    ShardedRecordReader,
    compute_read_split,
    create_read_info,
    sharded_batches,
)


class TestSplits:
    def test_property_full_non_overlapping_coverage(self):
        # TestReader.java:41-60: 1000 random totals; splits must tile the
        # range exactly.
        rng = np.random.default_rng(0)
        for _ in range(1000):
            total = int(rng.integers(0, 10_000))
            n = int(rng.integers(1, 20))
            pos = 0
            for i in range(n):
                start, length = compute_read_split(total, i, n)
                assert start == pos
                pos = start + length
            assert pos == total

    def test_read_info_maps_ranges_to_files(self):
        files = [("a", 10), ("b", 0), ("c", 25)]
        segs = [create_read_info(files, i, 3) for i in range(3)]
        # 35 bytes over 3 tasks: 12, 12, 11.
        flat = [(s.path, s.offset, s.length) for task in segs for s in task]
        assert flat == [
            ("a", 0, 10), ("c", 0, 2),       # task 0: 12
            ("c", 2, 12),                    # task 1: 12
            ("c", 14, 11),                   # task 2: 11
        ]

    def test_bad_args(self):
        with pytest.raises(ValueError):
            compute_read_split(10, 0, 0)
        with pytest.raises(ValueError):
            compute_read_split(10, 3, 3)


def _write_jsonl(path, ids):
    with open(path, "w") as f:
        for i in ids:
            f.write(json.dumps({"id": i, "pad": "x" * (i % 7)}) + "\n")


class TestJsonlReader:
    @pytest.mark.parametrize("num_tasks", [1, 2, 3, 5])
    def test_exactly_once_across_readers(self, tmp_path, num_tasks):
        files = []
        n = 0
        for fi, count in enumerate([57, 1, 0, 113]):
            p = tmp_path / f"part-{fi}.jsonl"
            _write_jsonl(p, range(n, n + count))
            files.append(str(p))
            n += count
        seen = []
        for t in range(num_tasks):
            with ShardedRecordReader(
                files, t, num_tasks, fmt="jsonl", batch_size=16
            ) as r:
                for batch in r:
                    seen.extend(rec["id"] for rec in batch)
        assert sorted(seen) == list(range(n))  # every record exactly once

    def test_shuffle_changes_order_not_content(self, tmp_path):
        p = tmp_path / "d.jsonl"
        _write_jsonl(p, range(200))
        with ShardedRecordReader(
            [str(p)], fmt="jsonl", batch_size=200, shuffle=True,
            shuffle_pool=64, seed=1,
        ) as r:
            got = [rec["id"] for rec in r.next_batch()]
        assert got != list(range(200))
        assert sorted(got) == list(range(200))


class TestTokenReader:
    def test_batches_and_alignment(self, tmp_path):
        rl, n_rec = 8, 103
        data = np.arange(rl * n_rec, dtype=np.uint16).reshape(n_rec, rl)
        p = tmp_path / "tokens.bin"
        data.tofile(p)
        seen = []
        for t in range(4):
            with ShardedRecordReader(
                [str(p)], t, 4, fmt="tokens", record_len=rl,
                dtype=np.uint16, batch_size=10,
            ) as r:
                for batch in r:
                    assert batch.shape[1] == rl
                    seen.extend(batch[:, 0].tolist())
        # exactly once: first token of each record identifies it
        assert sorted(seen) == [i * rl for i in range(n_rec)]

    def test_sharded_batches_places_on_mesh(self, tmp_path):
        import jax
        from tony_tpu.parallel.mesh import MeshSpec, build_mesh

        rl = 4
        data = np.arange(rl * 64, dtype=np.uint16).reshape(64, rl)
        p = tmp_path / "t.bin"
        data.tofile(p)
        mesh = build_mesh(MeshSpec(dp=8))
        with ShardedRecordReader(
            [str(p)], fmt="tokens", record_len=rl, batch_size=16
        ) as r:
            batches = list(sharded_batches(r, mesh))
        assert len(batches) == 4
        for b in batches:
            assert b.shape == (16, rl)
            assert len(b.sharding.device_set) == 8

    def test_device_prefetch_preserves_order_and_content(self):
        from tony_tpu.io import device_prefetch

        src = [np.full((4,), i, np.int32) for i in range(7)]
        out = list(device_prefetch(iter(src), depth=2))
        assert len(out) == 7
        for i, b in enumerate(out):
            np.testing.assert_array_equal(np.asarray(b), src[i])

    def test_device_prefetch_keeps_transfers_in_flight(self):
        """The pipeline must ISSUE batch N+1's device_put before batch N
        is consumed — observed through a tracking iterator: after pulling
        batch 0, the background transfer thread advances the source past
        batch 1 (depth=2 lookahead: the yielded batch plus one in
        flight), which is what overlaps H2D with the running step — and
        advances NO further until the consumer asks again (depth bounds
        total in-flight batches)."""
        import time

        from tony_tpu.io import device_prefetch

        pulled = []

        def src():
            for i in range(5):
                pulled.append(i)
                yield np.full((2,), i, np.int32)

        it = device_prefetch(src(), depth=2)
        first = next(it)
        np.testing.assert_array_equal(np.asarray(first), [0, 0])
        deadline = time.monotonic() + 5
        while len(pulled) < 2 and time.monotonic() < deadline:
            time.sleep(0.005)  # the transfer thread races ahead async
        assert pulled == [0, 1], pulled  # one batch already in flight
        time.sleep(0.05)
        assert pulled == [0, 1], pulled  # ...and the depth bound holds
        rest = list(it)
        assert len(rest) == 4
        assert pulled == [0, 1, 2, 3, 4]
        with pytest.raises(ValueError, match="depth"):
            next(device_prefetch(iter([np.zeros(1)]), depth=0))

    def test_sharded_batches_stream_trains_identically(self, tmp_path):
        """Streamed (double-buffered) batches are byte-identical, in
        order, to the underlying records — the bench's streamed-vs-
        synthetic comparison depends on this."""
        import jax
        from tony_tpu.io import device_prefetch  # noqa: F401
        from tony_tpu.parallel.mesh import MeshSpec, build_mesh

        rl = 8
        data = np.arange(rl * 32, dtype=np.uint16).reshape(32, rl)
        p = tmp_path / "t.bin"
        data.tofile(p)
        mesh = build_mesh(MeshSpec(dp=8))
        with ShardedRecordReader(
            [str(p)], fmt="tokens", record_len=rl, batch_size=8
        ) as r:
            got = np.concatenate(
                [np.asarray(b) for b in sharded_batches(r, mesh)]
            )
        np.testing.assert_array_equal(got, data)


class TestConsumerApis:
    """Schema introspection + spill-to-file (HdfsAvroFileSplitReader
    getSchemaJson:446-463, nextBatchFile/LocalSpill:503-542 analogues)."""

    def _jsonl(self, tmp_path, n=10):
        p = tmp_path / "d.jsonl"
        p.write_text("".join(
            json.dumps({"id": i, "text": f"t{i}"}) + "\n" for i in range(n)
        ))
        return str(p)

    def test_schema_json_jsonl(self, tmp_path):
        with ShardedRecordReader([self._jsonl(tmp_path)]) as r:
            schema = json.loads(r.schema_json())
        assert schema == {
            "format": "jsonl", "fields": {"id": "int", "text": "str"}
        }

    def test_schema_json_does_not_consume_records(self, tmp_path):
        with ShardedRecordReader(
            [self._jsonl(tmp_path, 6)], batch_size=100
        ) as r:
            r.schema_json()
            batch = r.next_batch()
        assert [rec["id"] for rec in batch] == list(range(6))

    def test_schema_json_tokens(self, tmp_path):
        p = tmp_path / "t.bin"
        np.arange(32, dtype=np.uint16).tofile(p)
        with ShardedRecordReader(
            [str(p)], fmt="tokens", record_len=8, dtype=np.uint16
        ) as r:
            schema = json.loads(r.schema_json())
        assert schema == {"format": "tokens", "dtype": "uint16",
                          "record_len": 8}

    def test_next_batch_file_tokens_mmap_ready(self, tmp_path):
        p = tmp_path / "t.bin"
        np.arange(64, dtype=np.uint16).tofile(p)
        with ShardedRecordReader(
            [str(p)], fmt="tokens", record_len=8, dtype=np.uint16,
            batch_size=4,
        ) as r:
            path = r.next_batch_file(tmp_path)
        arr = np.load(path, mmap_mode="r")
        assert arr.shape == (4, 8) and arr[0, 0] == 0

    def test_next_batch_file_jsonl_and_eof(self, tmp_path):
        with ShardedRecordReader(
            [self._jsonl(tmp_path, 3)], batch_size=10
        ) as r:
            path = r.next_batch_file(tmp_path)
            lines = open(path).read().splitlines()
            assert [json.loads(l)["id"] for l in lines] == [0, 1, 2]
            assert r.next_batch_file(tmp_path) is None


class TestNativeDecoder:
    """Native C++ data-plane kernels (native/tony_io.cc) pinned to the
    pure-Python paths; all tests skip when the library isn't built
    (`make -C native`)."""

    @pytest.fixture(autouse=True)
    def _need_native(self):
        from tony_tpu.io import native

        if not native.available():
            pytest.skip("libtony_io.so not built")

    def test_scan_record_starts_matches_python(self):
        from tony_tpu.io import native

        chunk = b'{"a":1}\n{"b":2}\n{"c":3}\npartial'
        got = native.scan_record_starts(chunk)
        want = [m + 1 for m in range(len(chunk) - 1) if chunk[m:m + 1] == b"\n"]
        assert got == want == [8, 16, 24]
        assert native.count_records(chunk) == 3
        assert native.scan_record_starts(b"") == []
        assert native.scan_record_starts(b"no newline") == []
        # trailing newline: no successor byte, so no start offset
        assert native.scan_record_starts(b"x\n") == []

    def test_token_read_matches_python_fallback(self, tmp_path, monkeypatch):
        p = tmp_path / "t.bin"
        rng = np.random.default_rng(0)
        data = rng.integers(0, 2**16, size=(67, 8)).astype(np.uint16)
        data.tofile(p)

        def read_all(force_fallback):
            from tony_tpu.io import native

            if force_fallback:
                monkeypatch.setattr(native, "available", lambda: False)
            r = ShardedRecordReader(
                [str(p)], fmt="tokens", record_len=8, dtype=np.uint16,
                batch_size=67,
            )
            try:
                return r.next_batch()
            finally:
                r.close()
                monkeypatch.undo()

        native_batch = read_all(False)
        python_batch = read_all(True)
        np.testing.assert_array_equal(native_batch, python_batch)
        np.testing.assert_array_equal(native_batch, data)

    def test_native_read_chunking_boundaries(self, tmp_path):
        # more records than one native chunk -> multiple preads
        p = tmp_path / "big.bin"
        n = ShardedRecordReader._CHUNK_RECORDS * 2 + 7
        data = np.arange(n * 4, dtype=np.uint16).reshape(n, 4)
        data.tofile(p)
        with ShardedRecordReader(
            [str(p)], fmt="tokens", record_len=4, dtype=np.uint16,
            batch_size=n,
        ) as r:
            batch = r.next_batch()
        np.testing.assert_array_equal(batch, data)

    def test_exactly_once_with_native_path(self, tmp_path):
        p = tmp_path / "s.bin"
        np.arange(40 * 4, dtype=np.uint16).tofile(p)
        seen = []
        for idx in range(3):
            with ShardedRecordReader(
                [str(p)], task_index=idx, num_tasks=3, fmt="tokens",
                record_len=4, dtype=np.uint16, batch_size=100,
            ) as r:
                b = r.next_batch()
                if b is not None:
                    seen.extend(int(row[0]) for row in b)
        assert sorted(seen) == [i * 4 for i in range(40)]

    def test_batches_are_writable_both_paths(self, tmp_path, monkeypatch):
        from tony_tpu.io import native

        p = tmp_path / "w.bin"
        np.arange(32, dtype=np.uint16).tofile(p)
        for force_py in (False, True):
            if force_py:
                monkeypatch.setattr(native, "available", lambda: False)
            with ShardedRecordReader(
                [str(p)], fmt="tokens", record_len=8, dtype=np.uint16,
                batch_size=2,
            ) as r:
                b = r.next_batch()
                b *= 2  # consumers mutate in place (e.g. masking)
            monkeypatch.undo()


# ---------------------------------------------------------------------------
# gs:// data plane: the reader opens remote corpora
# directly, the way the reference's reader opens HDFS
# (HdfsAvroFileSplitReader.java:347-416) — no manual staging.
# ---------------------------------------------------------------------------

@pytest.fixture
def gcs_emulator(tmp_path):
    from tony_tpu.cloud import set_default_storage
    from tony_tpu.cloud.gcs import FileObjectStorage

    store = FileObjectStorage(tmp_path / "objects")
    set_default_storage(store)
    yield store
    set_default_storage(None)


class TestGsReader:
    @pytest.mark.parametrize("num_tasks", [1, 2, 3])
    def test_jsonl_exactly_once_over_gs(self, gcs_emulator, num_tasks):
        """Two/three readers over gs:// shards: every record exactly once,
        including records straddling the byte-range boundaries (the
        split-brain rule must hold over ranged fetches too)."""
        uris, n = [], 0
        for fi, count in enumerate([41, 0, 87]):
            body = "".join(
                json.dumps({"id": i, "pad": "y" * (i % 11)}) + "\n"
                for i in range(n, n + count)
            ).encode()
            uri = f"gs://corpus/part-{fi}.jsonl"
            gcs_emulator.put_bytes(uri, body)
            uris.append(uri)
            n += count
        seen = []
        for t in range(num_tasks):
            with ShardedRecordReader(
                uris, t, num_tasks, fmt="jsonl", batch_size=16
            ) as r:
                for batch in r:
                    seen.extend(rec["id"] for rec in batch)
        assert sorted(seen) == list(range(n))

    def test_tokens_over_gs_match_local(self, gcs_emulator, tmp_path):
        rl, n_rec = 8, 103
        data = np.arange(rl * n_rec, dtype=np.uint16).reshape(n_rec, rl)
        local = tmp_path / "tokens.bin"
        data.tofile(local)
        gcs_emulator.put_bytes("gs://corpus/tokens.bin", local.read_bytes())
        for t in range(3):
            with ShardedRecordReader(
                [str(local)], t, 3, fmt="tokens", record_len=rl,
                dtype=np.uint16, batch_size=10,
            ) as lr, ShardedRecordReader(
                ["gs://corpus/tokens.bin"], t, 3, fmt="tokens",
                record_len=rl, dtype=np.uint16, batch_size=10,
            ) as gr:
                while True:
                    lb, gb = lr.next_batch(), gr.next_batch()
                    if lb is None:
                        assert gb is None
                        break
                    np.testing.assert_array_equal(lb, gb)

    def test_gs_token_batches_are_writable(self, gcs_emulator):
        gcs_emulator.put_bytes(
            "gs://corpus/w.bin", np.arange(32, dtype=np.uint16).tobytes()
        )
        with ShardedRecordReader(
            ["gs://corpus/w.bin"], fmt="tokens", record_len=8,
            dtype=np.uint16, batch_size=2,
        ) as r:
            b = r.next_batch()
            b *= 2

    def test_mixed_local_and_gs_paths(self, gcs_emulator, tmp_path):
        local = tmp_path / "a.jsonl"
        _write_jsonl(local, range(10))
        gcs_emulator.put_bytes("gs://corpus/b.jsonl", "".join(
            json.dumps({"id": i, "pad": ""}) + "\n" for i in range(10, 25)
        ).encode())
        seen = []
        for t in range(2):
            with ShardedRecordReader(
                [str(local), "gs://corpus/b.jsonl"], t, 2, fmt="jsonl",
                batch_size=7,
            ) as r:
                for batch in r:
                    seen.extend(rec["id"] for rec in batch)
        assert sorted(seen) == list(range(25))


class TestRangeLineStream:
    def test_lines_across_chunk_boundaries(self, gcs_emulator, monkeypatch):
        from tony_tpu.io.storage import RangeLineStream

        lines = [f"record-{i:04d}-" + "z" * (i % 13) for i in range(300)]
        body = ("\n".join(lines) + "\n").encode()
        gcs_emulator.put_bytes("gs://corpus/lines.txt", body)
        monkeypatch.setattr(RangeLineStream, "CHUNK", 37)  # force many fetches
        s = RangeLineStream("gs://corpus/lines.txt")
        got = []
        while True:
            line = s.readline()
            if not line:
                break
            got.append(line.decode().rstrip("\n"))
        assert got == lines
        assert s.tell() == len(body)

    def test_seek_one_byte_back_boundary_rule(self, gcs_emulator):
        from tony_tpu.io.storage import RangeLineStream

        body = b"aaaa\nbbbb\ncccc\n"
        gcs_emulator.put_bytes("gs://corpus/b.txt", body)
        s = RangeLineStream("gs://corpus/b.txt")
        # offset 5 is exactly the start of "bbbb": seeking one back and
        # reading a line must consume only the newline, keeping "bbbb"
        s.seek(4)
        assert s.readline() == b"\n"
        assert s.readline() == b"bbbb\n"
        assert s.tell() == 10


class TestJsonlBlocks:
    """Block-compressed jsonl container (io/blocks.py) — the Avro-
    container analogue (HdfsAvroFileSplitReader.java:190-240 sync-marker
    splits, :446-463 schema negotiation): compressed corpora must still
    split by byte range, read exactly once, and surface their schema."""

    def _write(self, path, n=100, codec="gzip", schema=None, block=16):
        from tony_tpu.io import write_jsonl_blocks

        recs = [{"id": i, "text": f"record-{i}" * 3} for i in range(n)]
        if codec == "zstd":
            pytest.importorskip("zstandard")
        wrote = write_jsonl_blocks(
            str(path), recs, codec=codec, block_records=block,
            schema=schema,
        )
        assert wrote == n
        return recs

    @pytest.mark.parametrize("codec", ["none", "gzip", "zstd"])
    def test_roundtrip_all_codecs(self, tmp_path, codec):
        p = tmp_path / f"c.{codec}.jblk"
        recs = self._write(p, codec=codec)
        with ShardedRecordReader(
            [str(p)], fmt="jsonl-blocks", batch_size=32
        ) as r:
            got = [rec for batch in r for rec in batch]
        assert got == recs

    def test_compression_actually_shrinks(self, tmp_path):
        pn, pz = tmp_path / "a", tmp_path / "b"
        self._write(pn, n=500, codec="none")
        self._write(pz, n=500, codec="zstd")
        assert pz.stat().st_size < pn.stat().st_size / 2

    @pytest.mark.parametrize("codec", ["gzip", "zstd"])
    def test_split_readers_each_record_exactly_once(self, tmp_path, codec):
        """4 byte-range readers over one compressed container: the sync-
        marker owner rule hands every block to exactly one reader even
        though ranges land mid-block."""
        p = tmp_path / "c.jblk"
        recs = self._write(p, n=200, codec=codec, block=8)
        seen = []
        for t in range(4):
            with ShardedRecordReader(
                [str(p)], t, 4, fmt="jsonl-blocks", batch_size=16
            ) as r:
                seen.extend(rec["id"] for b in r for rec in b)
        assert sorted(seen) == list(range(200))

    def test_schema_negotiated_from_header_without_data_read(self, tmp_path):
        import json as _json

        p = tmp_path / "s.jblk"
        self._write(p, schema={"id": "long", "text": "string"})
        with ShardedRecordReader(
            [str(p)], fmt="jsonl-blocks", batch_size=8
        ) as r:
            doc = _json.loads(r.schema_json())
        assert doc["codec"] == "gzip"
        assert doc["schema"] == {"id": "long", "text": "string"}

    def test_schema_falls_back_to_introspection(self, tmp_path):
        import json as _json

        p = tmp_path / "s2.jblk"
        self._write(p)  # no embedded schema
        with ShardedRecordReader(
            [str(p)], fmt="jsonl-blocks", batch_size=8
        ) as r:
            doc = _json.loads(r.schema_json())
        assert doc["fields"] == {"id": "int", "text": "str"}

    def test_schema_found_in_later_container(self, tmp_path):
        """Schema negotiation must consult EVERY container backing the
        reader, not just the first: here the first header is empty and
        only the second embeds a schema."""
        import json as _json

        p1 = tmp_path / "a.jblk"
        p2 = tmp_path / "b.jblk"
        self._write(p1)  # no embedded schema
        self._write(p2, schema={"id": "long", "text": "string"})
        with ShardedRecordReader(
            [str(p1), str(p2)], fmt="jsonl-blocks", batch_size=8
        ) as r:
            doc = _json.loads(r.schema_json())
        assert doc["schema"] == {"id": "long", "text": "string"}

    def test_corrupt_sync_candidate_skipped_by_crc(self, tmp_path):
        """Garbage bytes containing a fake SYNC marker (with junk lengths
        and CRC) between two real blocks must be skipped — the CRC +
        length guard is what makes marker collisions harmless."""
        from tony_tpu.io.blocks import SYNC, write_jsonl_blocks

        p = tmp_path / "k.jblk"
        write_jsonl_blocks(str(p), [{"id": 0}], block_records=1)
        tail_recs = [{"id": 1}]
        p2 = tmp_path / "tail.jblk"
        write_jsonl_blocks(str(p2), tail_recs, block_records=1)
        # splice: file = (whole first container) + fake sync + junk +
        # (second container's first block, stripped of its header)
        from tony_tpu.io.blocks import read_header

        _, _, data_start = read_header(str(p2))
        blob = (
            p.read_bytes()
            + SYNC + b"\xff" * 24          # implausible lengths + junk
            + p2.read_bytes()[data_start:]
        )
        p.write_bytes(blob)
        with ShardedRecordReader(
            [str(p)], fmt="jsonl-blocks", batch_size=8
        ) as r:
            got = [rec["id"] for b in r for rec in b]
        assert got == [0, 1]

    def test_non_container_file_fails_loudly(self, tmp_path):
        p = tmp_path / "plain.jsonl"
        p.write_text('{"id": 1}\n')
        with ShardedRecordReader(
            [str(p)], fmt="jsonl-blocks", batch_size=8
        ) as r:
            with pytest.raises(ValueError, match="bad magic"):
                r.schema_json()
        # and CONSUMING must raise too — a fetcher-thread failure must
        # never read as a clean (empty) end of shard
        with ShardedRecordReader(
            [str(p)], fmt="jsonl-blocks", batch_size=8
        ) as r:
            with pytest.raises(RuntimeError, match="NOT exhausted"):
                r.next_batch()
            # a caller that catches and retries must KEEP failing loudly,
            # not read the requeued sentinel as a clean end of shard
            with pytest.raises(RuntimeError, match="NOT exhausted"):
                r.next_batch()

    def test_gs_container_roundtrip(self, tmp_path, monkeypatch):
        """A gs:// container through the FileObjectStorage emulator: the
        writer PUTs the whole container, split readers range-read it."""
        import os

        from tony_tpu.cloud import set_default_storage
        from tony_tpu.cloud.gcs import FileObjectStorage

        set_default_storage(FileObjectStorage(tmp_path / "obj"))
        try:
            uri = "gs://corpus/train.jblk"
            recs = self._write(uri, n=60, codec="gzip", block=7)
            seen = []
            for t in range(2):
                with ShardedRecordReader(
                    [uri], t, 2, fmt="jsonl-blocks", batch_size=16
                ) as r:
                    seen.extend(rec["id"] for b in r for rec in b)
            assert sorted(seen) == list(range(60))
        finally:
            set_default_storage(None)


class TestJsonlBlocksEdges:
    def test_empty_container_reads_cleanly(self, tmp_path):
        from tony_tpu.io import write_jsonl_blocks

        p = tmp_path / "empty.jblk"
        assert write_jsonl_blocks(str(p), []) == 0
        with ShardedRecordReader(
            [str(p)], fmt="jsonl-blocks", batch_size=4
        ) as r:
            assert r.next_batch() is None

    def test_single_record_container(self, tmp_path):
        from tony_tpu.io import write_jsonl_blocks

        p = tmp_path / "one.jblk"
        write_jsonl_blocks(str(p), [{"id": 42}])
        with ShardedRecordReader(
            [str(p)], fmt="jsonl-blocks", batch_size=4
        ) as r:
            assert [rec["id"] for rec in r.next_batch()] == [42]

    def test_reader_more_tasks_than_blocks(self, tmp_path):
        """8 split readers over a 2-block container: most shards own no
        block and must come up empty instead of duplicating reads."""
        from tony_tpu.io import write_jsonl_blocks

        p = tmp_path / "few.jblk"
        write_jsonl_blocks(
            str(p), [{"id": i} for i in range(8)], block_records=4
        )
        seen = []
        for t in range(8):
            with ShardedRecordReader(
                [str(p)], t, 8, fmt="jsonl-blocks", batch_size=8
            ) as r:
                seen.extend(rec["id"] for b in r for rec in b)
        assert sorted(seen) == list(range(8))

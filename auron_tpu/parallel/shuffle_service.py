"""Host shuffle service: the RSS (remote-shuffle-service) tier.

The reference pushes shuffle data to Celeborn/Uniffle through
`RssPartitionWriterBase` — map tasks stream per-partition byte chunks to a
service, reducers fetch one merged stream per partition (reference:
datafusion-ext-plans/src/shuffle/rss.rs,
thirdparty/auron-celeborn-0.6/.../CelebornPartitionWriter.scala). On a TPU
pod the intra-slice exchange rides ICI all-to-all
(parallel/mesh_exchange.py); this tier is the complement for data that
exceeds slice HBM or must cross hosts without ICI: partition frames are
pushed to a service root on shared storage (NFS/FUSE-mounted object
store — the deployment substrate TPU pods already have for checkpoints),
and any host can read any partition back.

Layout (one directory per shuffle):
    root/shuffle_{id}/map_{m}.part        in-progress map output
    root/shuffle_{id}/map_{m}.data        committed map output
    root/shuffle_{id}/manifest           shuffle-level commit marker

A map output file (format v2, magic ``AUR2``) is a sequence of frame
records grouped by partition — each record ``<u32 len><u32 crc>`` +
frame bytes — followed by a trailer [per partition: run count +
(offset, length) runs] and a footer naming the trailer offset, the
partition count, the trailer's own CRC and the checksum algorithm id
(utils/checksum.py). Every fetch verifies the frame CRC before
deserializing: a flipped byte on storage surfaces as
``errors.ShuffleCorruption`` carrying the map id, which the RSS exchange
recovers by invalidating that map output and recomputing the map task —
never a blind reducer retry over the same corrupt bytes, never silently
wrong rows. v1 files (magic ``AURS``, no CRCs) are *rejected* with the
same corruption error, not misread.

Commits are atomic renames at two levels: per map output, and the
shuffle-level ``manifest`` naming the exact map count, so readers never
observe partial attempts OR stale map outputs from a previous attempt
with different parallelism. Map retries overwrite by map id (idempotent,
the engine's partition-granular recovery contract, SURVEY.md §5.3).
``commit_shuffle`` also sweeps orphaned ``.part`` files — an aborted map
attempt must not leak storage.

Fault-injection sites (runtime/faults.py): ``rss.write`` (buffered push
+ on-disk corruption after the CRC — durable bit rot), ``rss.flush``,
``rss.commit``, ``rss.fetch`` (fetch failure + in-flight corruption).
"""

from __future__ import annotations

import io
import os
import struct
import threading
from typing import Iterator, Optional

from auron_tpu import errors
from auron_tpu.utils import checksum as cks

#: v2 footer magic; v1 (``AURS``) files are rejected as corrupt
_TRAILER_MAGIC = b"AUR2"
_V1_MAGIC = b"AURS"
#: footer: <Q trailer_start><I num_partitions><I trailer_crc><B algo>
_FOOTER = struct.Struct("<QIIB")
#: per-frame record header (shared with the spill tier, utils/checksum.py)
_FRAME_HDR = cks.FRAME_HDR


class RssPartitionWriter:
    """Push-based writer for ONE map task's output across all partitions.

    Frames are buffered per partition and flushed to the map file grouped
    by partition id; `commit()` writes the offset trailer and atomically
    renames. The buffer bound makes host memory independent of map-output
    size (the push-based contract of the reference's RSS writers).

    Context-manager support guarantees no exception path leaves a
    ``.part`` file behind: exiting the ``with`` block without having
    committed — exception or not — aborts (abort after commit is a
    no-op)."""

    def __init__(self, service: "FileShuffleService", shuffle_id: int,
                 map_id: int, num_partitions: int,
                 buffer_bytes: int = 8 << 20):
        self.service = service
        self.shuffle_id = shuffle_id
        self.map_id = map_id
        self.num_partitions = num_partitions
        self.buffer_bytes = buffer_bytes
        self._algo = cks.write_algo()
        self._dir = service._shuffle_dir(shuffle_id)
        os.makedirs(self._dir, exist_ok=True)
        self._tmp = os.path.join(self._dir, f"map_{map_id}.part")
        self._final = os.path.join(self._dir, f"map_{map_id}.data")
        service._write_owner(self._dir)
        self._file = open(self._tmp, "wb")
        #: per-partition buffered frames awaiting a flush
        self._buffers: dict[int, list[bytes]] = {}
        self._buffered = 0
        #: per-partition list of (offset, length) runs already on disk
        self._runs: dict[int, list[tuple[int, int]]] = {}
        self._pos = 0
        self._committed = False
        #: commit artifacts the query journal records (runtime/journal):
        #: total committed file size and the trailer's CRC — the cheap
        #: resume-time validity check that needs only the footer
        self.committed_size = 0
        self.trailer_crc = 0

    def __enter__(self) -> "RssPartitionWriter":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        # uncommitted on exit — exception unwind OR a caller that never
        # reached commit() — is an abandoned attempt: abort
        self.abort()
        return False

    def write(self, partition: int, frame: bytes) -> None:
        assert not self._committed
        from auron_tpu.runtime import faults
        faults.maybe_fail("rss.write", errors.RssUnavailableError)
        # CRC here, not at flush time: the producer just serialized the
        # frame, so the bytes are cache-hot — the hardware CRC runs at
        # its warm rate instead of re-streaming a cold flush buffer
        crc = cks.compute(frame, self._algo)
        self._buffers.setdefault(partition, []).append((frame, crc))
        self._buffered += len(frame)
        if self._buffered >= self.buffer_bytes:
            self._flush()

    def _flush(self) -> None:
        from auron_tpu.obs import trace
        from auron_tpu.runtime import faults
        faults.maybe_fail("rss.flush", errors.RssUnavailableError)
        with trace.span("shuffle", "rss.flush", shuffle=self.shuffle_id,
                        map=self.map_id, bytes=self._buffered):
            self._flush_inner()

    def _flush_inner(self) -> None:
        from auron_tpu.runtime import faults
        for p in sorted(self._buffers):
            frames = self._buffers[p]
            start = self._pos
            for fr, crc in frames:
                # corruption injects AFTER the CRC over the clean bytes:
                # durable bit rot is the integrity layer's problem
                payload = faults.maybe_corrupt("rss.write", fr)
                self._file.write(_FRAME_HDR.pack(len(fr), crc))
                self._file.write(payload)
                self._pos += _FRAME_HDR.size + len(fr)
            self._runs.setdefault(p, []).append((start, self._pos - start))
        self._buffers = {}
        self._buffered = 0

    def commit(self) -> None:
        """Flush, append the partition-run trailer, atomically publish."""
        from auron_tpu.obs import trace
        from auron_tpu.runtime import faults
        faults.maybe_fail("rss.commit", errors.RssUnavailableError)
        with trace.span("shuffle", "rss.commit", shuffle=self.shuffle_id,
                        map=self.map_id, bytes=self._pos):
            self._commit_inner()

    def _commit_inner(self) -> None:
        self._flush()
        trailer_start = self._pos
        # trailer: per partition, run count then (offset, length) pairs —
        # assembled in memory so its own CRC rides the footer
        trailer = io.BytesIO()
        for p in range(self.num_partitions):
            runs = self._runs.get(p, [])
            trailer.write(struct.pack("<I", len(runs)))
            for off, ln in runs:
                trailer.write(struct.pack("<QQ", off, ln))
        tbytes = trailer.getvalue()
        self._file.write(tbytes)
        tcrc = cks.compute(tbytes, self._algo)
        self._file.write(_FOOTER.pack(trailer_start, self.num_partitions,
                                      tcrc, self._algo))
        self._file.write(_TRAILER_MAGIC)
        self._file.close()
        os.replace(self._tmp, self._final)   # atomic commit
        self._committed = True
        self.committed_size = (trailer_start + len(tbytes)
                               + _FOOTER.size + len(_TRAILER_MAGIC))
        self.trailer_crc = tcrc

    def abort(self) -> None:
        if not self._committed:
            try:
                self._file.close()
                os.unlink(self._tmp)
            except OSError:
                pass


#: roots already startup-swept by THIS process (one sweep per root per
#: process: the sweep targets a crashed PREDECESSOR's leftovers, and a
#: root is typically re-opened many times per query)
_SWEPT_ROOTS: set = set()
_SWEPT_LOCK = threading.Lock()


class FileShuffleService:
    """Shared-storage shuffle service. Each host creates its own instance
    over the same root; no coordination beyond the filesystem's atomic
    renames is needed.

    Every shuffle directory carries a ``.owner`` tag
    (``utils/liveness``: host:pid:epoch of the writing process), and
    service construction runs a STARTUP SWEEP over the root: a crashed
    predecessor's ``.part`` files are removed, and — in the default
    ``orphan_sweep=True`` mode — its whole UNCOMMITTED shuffle
    directories too (no manifest = no reader can ever observe them).
    ``orphan_sweep="parts"`` restricts the sweep to ``.part`` files
    (journal-managed roots: the journal's own sweep owns whole-dir
    lifecycle there, because a dead process's partially-committed maps
    are exactly what resume reuses). Liveness is pid+epoch checked and
    host-scoped, so a live writer — this process included — is never
    swept; unowned directories (pre-sweep format) are left alone."""

    def __init__(self, root: str, orphan_sweep=True):
        self.root = root
        os.makedirs(root, exist_ok=True)
        #: shuffle dirs this service already owner-stamped (one .owner
        #: read + liveness probe per dir, not per map writer)
        self._stamped: set = set()
        self._stamped_lock = threading.Lock()
        if orphan_sweep:
            # full-mode roots are memoized process-wide (a root is
            # re-opened many times per query and the sweep targets a
            # crashed PREDECESSOR); parts-mode roots are per-query
            # journal run dirs — unique per query, so memoizing them
            # would grow the set forever, and the liveness-gated .part
            # sweep is repeat-safe and near-free on a fresh dir
            first = True
            if orphan_sweep is True:
                with _SWEPT_LOCK:
                    first = root not in _SWEPT_ROOTS
                    _SWEPT_ROOTS.add(root)
            if first:
                self.sweep_dead_owners(
                    remove_uncommitted=(orphan_sweep is True))

    def _shuffle_dir(self, shuffle_id: int) -> str:
        return os.path.join(self.root, f"shuffle_{shuffle_id}")

    def _write_owner(self, shuffle_dir: str) -> None:
        """Stamp (or adopt) the directory's owner tag: written when
        absent or when the recorded owner is provably dead (a resumed
        query adopting a crashed predecessor's partial shuffle).  Memo
        per (service, dir): a wide exchange opens one writer per map —
        one .owner read + liveness probe per DIR, not per map."""
        from auron_tpu.utils import liveness
        with self._stamped_lock:
            if shuffle_dir in self._stamped:
                return
            self._stamped.add(shuffle_dir)
        path = os.path.join(shuffle_dir, ".owner")
        try:
            with open(path) as f:
                if liveness.is_live(f.read().strip()):
                    return
        except OSError:
            pass
        try:
            with open(path, "w") as f:
                f.write(liveness.own_tag())
        except OSError:   # pragma: no cover - best-effort tag
            pass

    def sweep_dead_owners(self, remove_uncommitted: bool = True) -> int:
        """The startup sweep (see class docstring); returns artifacts
        removed, counted on ``auron_rss_orphans_swept_total``."""
        import shutil

        from auron_tpu.utils import liveness
        removed = 0
        try:
            entries = sorted(os.listdir(self.root))
        except OSError:
            return 0
        for name in entries:
            d = os.path.join(self.root, name)
            if not (name.startswith("shuffle_") and os.path.isdir(d)):
                continue
            try:
                with open(os.path.join(d, ".owner")) as f:
                    owner = f.read().strip()
            except OSError:
                continue   # unowned (pre-sweep format): conservative
            if liveness.is_live(owner):
                continue
            committed = os.path.exists(os.path.join(d, "manifest"))
            if remove_uncommitted and not committed:
                shutil.rmtree(d, ignore_errors=True)
                removed += 1
                continue
            for f in os.listdir(d):
                if f.endswith(".part"):
                    try:
                        os.unlink(os.path.join(d, f))
                        removed += 1
                    except OSError:
                        pass
        liveness.note_swept("auron_rss_orphans_swept_total", removed,
                            self.root, "RSS")
        return removed

    def partition_writer(self, shuffle_id: int, map_id: int,
                         num_partitions: int,
                         buffer_bytes: int = 8 << 20) -> RssPartitionWriter:
        return RssPartitionWriter(self, shuffle_id, map_id, num_partitions,
                                  buffer_bytes)

    # -- shuffle-level commit ------------------------------------------------

    def begin_shuffle(self, shuffle_id: int) -> None:
        """Invalidate any previous attempt: a re-planned stage (different
        map parallelism, AQE) must not leave stale map outputs visible."""
        d = self._shuffle_dir(shuffle_id)
        try:
            os.unlink(os.path.join(d, "manifest"))
        except OSError:
            pass

    def commit_shuffle(self, shuffle_id: int, num_maps: int) -> None:
        d = self._shuffle_dir(shuffle_id)
        os.makedirs(d, exist_ok=True)
        self._write_owner(d)
        tmp = os.path.join(d, "manifest.part")
        with open(tmp, "w") as f:
            f.write(str(num_maps))
        os.replace(tmp, os.path.join(d, "manifest"))
        # committed shuffles carry no in-progress files: sweep orphans
        # from aborted/crashed map attempts (the .part leak audit)
        self.sweep_parts(shuffle_id)

    def sweep_parts(self, shuffle_id: int) -> int:
        """Remove orphaned ``.part`` files (crashed map attempts that
        never reached abort()); returns how many were removed."""
        d = self._shuffle_dir(shuffle_id)
        removed = 0
        if not os.path.isdir(d):
            return removed
        for f in os.listdir(d):
            if f.endswith(".part"):
                try:
                    os.unlink(os.path.join(d, f))
                    removed += 1
                except OSError:
                    pass
        return removed

    def invalidate_map(self, shuffle_id: int, map_id: int) -> None:
        """Drop ONE committed map output (corruption recovery: the map
        task recomputes and re-commits under the same id; the manifest —
        which names only the map COUNT — stays valid throughout)."""
        try:
            os.unlink(os.path.join(self._shuffle_dir(shuffle_id),
                                   f"map_{map_id}.data"))
        except OSError:
            pass

    def map_outputs(self, shuffle_id: int) -> list[str]:
        """Committed map output files present on storage (diagnostics;
        readers use :meth:`committed_maps`, which honors the manifest)."""
        d = self._shuffle_dir(shuffle_id)
        if not os.path.isdir(d):
            return []
        return sorted(os.path.join(d, f) for f in os.listdir(d)
                      if f.endswith(".data"))

    def committed_maps(self, shuffle_id: int) -> list[str]:
        """Paths of EXACTLY the map outputs the manifest names; [] when the
        shuffle is not (yet) committed."""
        d = self._shuffle_dir(shuffle_id)
        num_maps = self.manifest_maps(shuffle_id)
        return [os.path.join(d, f"map_{m}.data") for m in range(num_maps)]

    def manifest_maps(self, shuffle_id: int) -> int:
        """Map count the shuffle-level manifest names; 0 when the
        shuffle is not (yet) committed."""
        try:
            with open(os.path.join(self._shuffle_dir(shuffle_id),
                                   "manifest")) as f:
                # graft: disable=GL001 -- a manifest file's text, host data
                return int(f.read().strip())
        except (OSError, ValueError):
            return 0

    def map_output_stat(self, shuffle_id: int,
                        map_id: int) -> Optional[tuple[int, int]]:
        """(size, trailer_crc) of one committed map output — the query
        journal's cheap resume-time validity probe (reads only the
        footer, never the frames; frame CRCs still verify on every
        fetch).  None when the file is missing or its footer is not a
        valid v2 trailer."""
        path = os.path.join(self._shuffle_dir(shuffle_id),
                            f"map_{map_id}.data")
        foot = _FOOTER.size + len(_TRAILER_MAGIC)
        try:
            with open(path, "rb") as f:
                f.seek(0, os.SEEK_END)
                size = f.tell()
                if size < foot:
                    return None
                f.seek(size - foot)
                tail = f.read(foot)
        except OSError:
            return None
        if tail[-4:] != _TRAILER_MAGIC:
            return None
        _start, _nparts, trailer_crc, _algo = \
            _FOOTER.unpack(tail[:_FOOTER.size])
        return size, trailer_crc

    # -- read side ------------------------------------------------------------

    def partition_frames(self, shuffle_id: int,
                         partition: int) -> Iterator[bytes]:
        """All committed map outputs' frames for one partition, reading
        only that partition's byte runs (offset-indexed fetch). One read
        for the whole trailer + one per run — no per-entry round trips
        (matters on NFS/FUSE substrates). Every frame is CRC-verified;
        a mismatch raises ShuffleCorruption naming the map."""
        for map_id, path in enumerate(self.committed_maps(shuffle_id)):
            yield from self.map_partition_frames(shuffle_id, map_id,
                                                 partition)

    def map_partition_frames(self, shuffle_id: int, map_id: int,
                             partition: int) -> list[bytes]:
        """Verified frames of ONE committed map output for one partition
        — the recovery granularity: the RSS exchange fetches map by map
        so a ShuffleCorruption can recompute exactly the corrupt map
        without re-yielding earlier maps' data."""
        from auron_tpu.obs import trace
        from auron_tpu.runtime import faults
        path = os.path.join(self._shuffle_dir(shuffle_id),
                            f"map_{map_id}.data")
        with trace.span("shuffle", "rss.fetch", shuffle=shuffle_id,
                        map=map_id, partition=partition) as sp:
            frames = self._map_partition_frames(shuffle_id, map_id,
                                                partition, path)
            sp.set(frames=len(frames),
                   bytes=sum(len(f) for f in frames))
            return frames

    def _map_partition_frames(self, shuffle_id: int, map_id: int,
                              partition: int, path: str) -> list[bytes]:
        from auron_tpu.runtime import faults
        faults.maybe_fail("rss.fetch", errors.RssUnavailableError)

        def corrupt(msg):
            return errors.ShuffleCorruption(
                f"{msg} (shuffle {shuffle_id} map {map_id}: {path})",
                shuffle_id=shuffle_id, map_id=map_id, path=path,
                site="rss.fetch")

        frames: list[bytes] = []
        try:
            f = open(path, "rb")
        except FileNotFoundError as e:
            # a committed map output that is GONE (invalidated by a
            # corruption recovery that died before re-committing, or
            # external deletion) is recovered exactly like a corrupt
            # one: map recompute rewrites it — never an unclassified
            # OSError out of the fetch path
            raise corrupt("map output missing from storage") from e
        with f:
            foot = _FOOTER.size + len(_TRAILER_MAGIC)
            f.seek(0, os.SEEK_END)
            size = f.tell()
            if size < foot:
                raise corrupt("map output truncated below footer size")
            f.seek(size - foot)
            tail = f.read(foot)
            if tail[-4:] != _TRAILER_MAGIC:
                if tail[-4:] == _V1_MAGIC:
                    raise corrupt("unchecksummed v1 map output rejected "
                                  "(recompute rewrites it at v2)")
                raise corrupt("bad map-output trailer magic")
            trailer_start, num_parts, trailer_crc, algo = \
                _FOOTER.unpack(tail[:_FOOTER.size])
            if partition >= num_parts:
                return frames
            if trailer_start > size - foot:
                raise corrupt("map-output trailer offset out of range")
            f.seek(trailer_start)
            trailer = f.read(size - foot - trailer_start)
            cks.verify_or_raise(trailer, trailer_crc, algo, corrupt,
                                what="map-output trailer")
            pos = 0
            runs = []
            try:
                for p in range(num_parts):
                    (nruns,) = struct.unpack_from("<I", trailer, pos)
                    pos += 4
                    if p == partition:
                        runs = [struct.unpack_from("<QQ", trailer,
                                                   pos + 16 * r)
                                for r in range(nruns)]
                        break
                    pos += 16 * nruns
            except struct.error as e:
                raise corrupt("map-output trailer truncated") from e
            for off, ln in runs:
                f.seek(off)
                blob = f.read(ln)
                bpos = 0
                while bpos < ln:
                    try:
                        flen, crc = _FRAME_HDR.unpack_from(blob, bpos)
                    except struct.error as e:
                        raise corrupt("frame header truncated") from e
                    bpos += _FRAME_HDR.size
                    frame = blob[bpos:bpos + flen]
                    if len(frame) != flen:
                        raise corrupt("frame body truncated")
                    frame = faults.maybe_corrupt("rss.fetch", frame)
                    cks.verify_or_raise(frame, crc, algo, corrupt)
                    frames.append(frame)
                    bpos += flen
        return frames

    def delete_shuffle(self, shuffle_id: int) -> None:
        import shutil
        shutil.rmtree(self._shuffle_dir(shuffle_id), ignore_errors=True)

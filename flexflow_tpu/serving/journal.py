"""Write-ahead request journal: durable serving past the process boundary.

The resilience contract below this file (scheduler/router/faults) stops
at the process: every injected fault retires one request or degrades one
path, but an engine-process crash loses every in-flight stream and its
committed tokens. Orca-style iteration-level scheduling is exactly what
makes recovery cheap — a request's restartable state between iterations
is just (prompt, committed tokens, cursor) — so this module journals
that state as it is created and rebuilds it after a crash:

* **submit records** — rid, client request-key, prompt, sampling/limit
  params, tenant/class/adapter — appended the moment the scheduler
  accepts (or strict=False-rejects) a request;
* **commit records** — the accepted token RUN per request per host
  sync, written at the reconcile grain: a verify or tree-verify step
  (a multi-token commit) journals its whole accepted run as one record, a
  plain decode one token — the journal's granularity is the engine's,
  not per-token;
* **terminal records** — final status + error, written by `_finalize`
  (the scheduler's only terminal transition) so no request can end
  without a durable verdict;
* **snapshot records** — an optional journal-referenced copy of a
  request's committed KV pages (`PagedKVCache.snapshot_swap`, the
  non-destructive sibling of `export_swap`), letting recovery restore
  KV over the swap-in path instead of recomputing when the cost model
  prices the copy under the recompute.

Framing is torn-tail-tolerant by construction: one record per line,
`<crc32 hex> <json>\\n`. A crash mid-append leaves at most one partial
final line; the reader verifies each line's CRC and JSON and drops ONLY
a broken LAST line (counted as torn) — a broken interior line is real
corruption and raises. fsync policy (`--journal-fsync`):

* ``commit`` — flush + fsync after every record (durability per event);
* ``batch`` — flush + fsync once per host sync (the default: one
  fsync per reconcile, the same grain the commits are batched at);
* ``off`` — flush to the OS per host sync, never fsync (survives a
  process crash, not a host power loss).

**Journal-before-publish** (fxlint FX111): the only writer of a
request's stream-visible token list (`Request.generated`) is the
scheduler's `_emit`, which notes each token here BEFORE the front
door's published-cursor diff can observe it; the journal flush runs
inside `scheduler.step()`, the publish after it returns. A token a
client saw is therefore always a token the journal recorded, which is
what makes the restart contract exact: deterministic greedy decode
re-derives everything past the committed cursor, the published-cursor
dedup in frontend/server.py replays everything before it, and the
client sees no duplicates and no gaps.

A journal WRITE failure (disk full, injected `journal_fail` fault)
degrades, never kills: the journal marks itself degraded, stops
appending, and serving continues undurable — availability over
durability, with the degradation visible in `degraded_reason`.
"""

from __future__ import annotations

import base64
import dataclasses
import json
import os
import zlib
from typing import Dict, List, Optional, Tuple

import numpy as np

__all__ = [
    "JournalCorrupt",
    "RequestJournal",
    "read_journal",
    "RecoveredRequest",
    "RecoveryState",
    "recover_journal",
    "readmit",
    "encode_swap_record",
    "decode_swap_record",
    "FSYNC_MODES",
]

FSYNC_MODES = ("commit", "batch", "off")


class JournalCorrupt(ValueError):
    """An INTERIOR journal record failed its CRC or JSON parse — not a
    torn tail (which the reader tolerates) but real corruption."""


# -- KV snapshot (de)serialization --------------------------------------------


def _enc_array(a: np.ndarray) -> Dict[str, object]:
    a = np.ascontiguousarray(a)
    return {
        "b": base64.b64encode(a.tobytes()).decode("ascii"),
        "dtype": str(a.dtype),
        "shape": list(a.shape),
    }


def _dec_array(d: Dict[str, object]) -> np.ndarray:
    buf = base64.b64decode(d["b"])
    return np.frombuffer(buf, dtype=np.dtype(d["dtype"])).reshape(
        [int(s) for s in d["shape"]]
    )


def encode_swap_record(rec: Dict[str, object]) -> Dict[str, object]:
    """JSON-encodable form of a `snapshot_swap`/`export_swap` record:
    the per-layer numpy pools become base64 blobs keyed by stringified
    layer guid; scalars and the geometry fingerprint pass through."""
    out: Dict[str, object] = {}
    for pool in ("k", "v", "k_scale", "v_scale"):
        out[pool] = {
            str(g): _enc_array(np.asarray(a)) for g, a in rec[pool].items()
        }
    for key in ("length", "pages", "bytes", "gen_len"):
        if key in rec:
            out[key] = int(rec[key])
    fp = rec.get("fingerprint")
    if fp is not None:
        out["fingerprint"] = [list(fp[0])] + [fp[1], fp[2], fp[3], fp[4]]
    return out


def decode_swap_record(doc: Dict[str, object]) -> Dict[str, object]:
    """Inverse of `encode_swap_record`, restoring the exact record
    shape `PagedKVCache.import_swap` validates (tuple fingerprint,
    int-guid-keyed numpy pools)."""
    rec: Dict[str, object] = {}
    for pool in ("k", "v", "k_scale", "v_scale"):
        rec[pool] = {
            int(g): _dec_array(d) for g, d in doc.get(pool, {}).items()
        }
    for key in ("length", "pages", "bytes", "gen_len"):
        if key in doc:
            rec[key] = int(doc[key])
    fp = doc.get("fingerprint")
    if fp is not None:
        rec["fingerprint"] = (
            tuple(fp[0]),
            int(fp[1]),
            int(fp[2]),
            int(fp[3]),
            str(fp[4]),
        )
    return rec


# -- the journal --------------------------------------------------------------


def _frame(payload: Dict[str, object]) -> bytes:
    body = json.dumps(payload, sort_keys=True, separators=(",", ":"))
    crc = zlib.crc32(body.encode("utf-8")) & 0xFFFFFFFF
    return f"{crc:08x} {body}\n".encode("utf-8")


def _unframe(line: bytes) -> Optional[Dict[str, object]]:
    """Decoded payload, or None when the line is broken (torn or
    corrupt — the caller decides which by position)."""
    try:
        text = line.decode("utf-8")
        crc_hex, body = text.split(" ", 1)
        body = body.rstrip("\n")
        if len(crc_hex) != 8:
            return None
        if int(crc_hex, 16) != (zlib.crc32(body.encode("utf-8")) & 0xFFFFFFFF):
            return None
        doc = json.loads(body)
        return doc if isinstance(doc, dict) else None
    except (ValueError, UnicodeDecodeError):
        return None


class RequestJournal:
    """Append-only write-ahead journal over one file. The scheduler is
    the writer: `submitted` at admission-queue entry, `note` per emitted
    token (buffered), `commit_pending` once per host sync (one commit
    record per request with fresh tokens), `finalize` at the terminal
    transition, `snapshot` when a KV snapshot is taken. A front door
    reads it back with `recover_journal` after a crash.

    `injector` threads the chaos harness's `maybe_journal_fail` through
    every append; `registry` (a telemetry.MetricsRegistry) keeps the
    `serve_journal_bytes` gauge current."""

    def __init__(
        self,
        path: str,
        fsync: str = "batch",
        injector=None,
        registry=None,
    ):
        if fsync not in FSYNC_MODES:
            raise ValueError(
                f"journal fsync must be one of {FSYNC_MODES}, got {fsync!r}"
            )
        self.path = str(path)
        self.fsync = fsync
        self.injector = injector
        self._f = open(self.path, "ab")
        self.bytes_written = int(self._f.tell())
        self.records_written = 0
        self.degraded = False
        self.degraded_reason: Optional[str] = None
        # rid -> tokens emitted since that rid's last commit record
        self._pending: Dict[int, List[int]] = {}
        self._gauge = None
        if registry is not None:
            # pre-create the whole durability catalog, not just our
            # gauge: recovery metrics are read AFTER a crash, when an
            # absent series is indistinguishable from a zero one
            from flexflow_tpu.telemetry.registry import (
                register_durability_metrics,
            )

            register_durability_metrics(registry)
            self._gauge = registry.gauge("serve_journal_bytes")
            self._gauge.set(self.bytes_written)

    # -- write path ----------------------------------------------------------

    def _append(self, payload: Dict[str, object]) -> bool:
        """One framed record. Returns False (and enters degraded mode)
        on an injected or real write failure — the serving path never
        raises out of a journal append."""
        if self.degraded:
            return False
        fail = getattr(self.injector, "maybe_journal_fail", None)
        if fail is not None and fail():
            self._degrade("injected journal write failure")
            return False
        try:
            data = _frame(payload)
            self._f.write(data)
            if self.fsync == "commit":
                self._f.flush()
                os.fsync(self._f.fileno())
        except OSError as e:
            self._degrade(f"journal write failed: {e!r}")
            return False
        self.bytes_written += len(data)
        self.records_written += 1
        if self._gauge is not None:
            self._gauge.set(self.bytes_written)
        return True

    def _degrade(self, reason: str) -> None:
        self.degraded = True
        self.degraded_reason = reason
        self._pending.clear()

    def _sync(self) -> None:
        """Batch-grain durability point (one per host sync)."""
        if self.degraded:
            return
        try:
            self._f.flush()
            if self.fsync == "batch":
                os.fsync(self._f.fileno())
        except OSError as e:
            self._degrade(f"journal flush failed: {e!r}")

    def submitted(self, req) -> None:
        """Submit record: everything a restart needs to rebuild and
        re-validate the request, including the client request-key the
        idempotent-resubmission dedup matches on."""
        self._append(
            {
                "type": "submit",
                "rid": int(req.rid),
                "key": getattr(req, "request_key", None),
                "prompt": [int(t) for t in req.prompt],
                "max_new_tokens": int(req.max_new_tokens),
                "eos_token": (
                    int(req.eos_token) if req.eos_token is not None else None
                ),
                "deadline_s": (
                    float(req.deadline_s)
                    if req.deadline_s is not None
                    else None
                ),
                "tenant": req.tenant,
                "cls": req.priority_class,
                "adapter_id": int(req.adapter_id),
                # a RECOVERED request re-enters with its committed run
                # already in `generated`; carrying it in the new submit
                # record makes a second crash-recovery fold correctly
                # (the fresh submit would otherwise reset the cursor)
                "committed": [int(t) for t in req.generated],
            }
        )
        self._sync()

    def note(self, rid: int, token: int) -> None:
        """Buffer one committed token; `commit_pending` writes the run.
        Called by the scheduler's `_emit` — the blessed stream writer
        (fxlint FX111) — so every stream-visible token passes through
        here before the front door can publish it."""
        if self.degraded:
            return
        self._pending.setdefault(int(rid), []).append(int(token))

    def commit_pending(self, iteration: int) -> None:
        """One commit record per request with fresh tokens — the
        per-host-sync grain: a verify or tree-verify round's whole
        accepted run (a multi-token commit) lands as one record."""
        if self.degraded or not self._pending:
            return
        # detach the batch first: a write failure mid-loop degrades the
        # journal (which clears `_pending`) — iterating the live dict
        # here would blow up instead of degrading gracefully
        pending, self._pending = self._pending, {}
        for rid in sorted(pending):
            run = pending[rid]
            if not run:
                continue
            if not self._append(
                {
                    "type": "commit",
                    "rid": rid,
                    "tokens": run,
                    "it": int(iteration),
                }
            ):
                return  # degraded: the rest of the batch is lost with it
        self._sync()

    def finalize(
        self,
        rid: int,
        status: str,
        error: Optional[str] = None,
        iteration: int = -1,
    ) -> None:
        """Terminal record, preceded by the rid's still-buffered commit
        run (a request must never end with published-but-unjournaled
        tokens)."""
        run = self._pending.pop(int(rid), None)
        if run:
            self._append(
                {
                    "type": "commit",
                    "rid": int(rid),
                    "tokens": run,
                    "it": int(iteration),
                }
            )
        self._append(
            {
                "type": "terminal",
                "rid": int(rid),
                "status": str(status),
                "error": error,
            }
        )
        self._sync()

    def snapshot(self, rid: int, record: Dict[str, object]) -> None:
        """Journal-referenced KV snapshot (from `snapshot_swap`): the
        latest one per rid wins at recovery, and is honored only when
        its `gen_len` still matches the committed run (commits past the
        snapshot make restoring it a double-decode — recompute wins)."""
        self._append(
            {
                "type": "snapshot",
                "rid": int(rid),
                "record": encode_swap_record(record),
            }
        )
        self._sync()

    def close(self) -> None:
        """Close the file WITHOUT flushing pending token runs: pending
        tokens at close time only exist mid-iteration (a crash path),
        and committing them here would fake a durability the crash
        didn't have — a graceful shutdown's pending buffer is empty
        because `_end_iteration` flushed it."""
        self._pending.clear()
        try:
            self._f.close()
        except OSError:
            pass


# -- read / recovery ----------------------------------------------------------


def read_journal(path: str) -> Tuple[List[Dict[str, object]], int]:
    """(records, torn): every valid record in order, plus how many
    trailing torn records were dropped (0 or 1 — the framing makes more
    than one impossible without interior corruption, which raises
    JournalCorrupt)."""
    with open(path, "rb") as f:
        raw = f.read()
    lines = raw.split(b"\n")
    if lines and lines[-1] == b"":
        lines.pop()
    records: List[Dict[str, object]] = []
    for i, line in enumerate(lines):
        doc = _unframe(line + b"\n")
        if doc is None:
            if i == len(lines) - 1:
                return records, 1  # torn tail: drop only the torn record
            raise JournalCorrupt(
                f"{path}: corrupt interior record at line {i + 1}"
            )
        records.append(doc)
    return records, 0


@dataclasses.dataclass
class RecoveredRequest:
    """One live (non-terminal) request rebuilt from the journal: the
    recompute cursor is (prompt, committed); `snapshot` is the decoded
    KV record when one is usable."""

    rid: int
    key: Optional[str]
    prompt: List[int]
    max_new_tokens: int
    eos_token: Optional[int]
    deadline_s: Optional[float]
    tenant: str = ""
    priority_class: str = ""
    adapter_id: int = -1
    committed: List[int] = dataclasses.field(default_factory=list)
    snapshot: Optional[Dict[str, object]] = None

    @property
    def complete(self) -> bool:
        """The committed run already satisfies the request's stopping
        rule (crash after the last commit, before/without its terminal
        record) — re-admitting would emit a duplicate token."""
        if len(self.committed) >= self.max_new_tokens:
            return True
        return bool(
            self.committed
            and self.eos_token is not None
            and self.committed[-1] == self.eos_token
        )


@dataclasses.dataclass
class RecoveryState:
    """What a fresh front door / engine rebuilds from: the live set
    with recompute cursors, the terminal verdicts (for request-key
    dedup of retried submits), and the rid watermark."""

    live: Dict[int, RecoveredRequest]
    terminals: Dict[int, Dict[str, object]]  # rid -> {status,error,tokens,key}
    key_to_rid: Dict[str, int]
    next_rid: int
    torn: int
    records: int

    @property
    def replayed_tokens(self) -> int:
        return sum(len(r.committed) for r in self.live.values())


def recover_journal(path: str) -> RecoveryState:
    """Fold the journal into the live set: submits open requests,
    commits extend their committed runs, terminals close them (keeping
    status + tokens for dedup replay), snapshots attach the latest KV
    record. A torn tail drops only the torn record."""
    records, torn = read_journal(path)
    live: Dict[int, RecoveredRequest] = {}
    terminals: Dict[int, Dict[str, object]] = {}
    key_to_rid: Dict[str, int] = {}
    next_rid = 0
    for rec in records:
        rtype = rec.get("type")
        rid = int(rec.get("rid", -1))
        next_rid = max(next_rid, rid + 1)
        if rtype == "submit":
            live[rid] = RecoveredRequest(
                rid=rid,
                key=rec.get("key"),
                prompt=[int(t) for t in rec.get("prompt", ())],
                max_new_tokens=int(rec.get("max_new_tokens", 16)),
                eos_token=(
                    int(rec["eos_token"])
                    if rec.get("eos_token") is not None
                    else None
                ),
                deadline_s=rec.get("deadline_s"),
                tenant=rec.get("tenant", ""),
                priority_class=rec.get("cls", ""),
                adapter_id=int(rec.get("adapter_id", -1)),
                committed=[int(t) for t in rec.get("committed", ())],
            )
            if rec.get("key"):
                key_to_rid[str(rec["key"])] = rid
        elif rtype == "commit":
            rr = live.get(rid)
            if rr is not None:
                rr.committed.extend(int(t) for t in rec.get("tokens", ()))
        elif rtype == "terminal":
            rr = live.pop(rid, None)
            terminals[rid] = {
                "status": rec.get("status"),
                "error": rec.get("error"),
                "tokens": list(rr.committed) if rr is not None else [],
                "key": rr.key if rr is not None else None,
            }
        elif rtype == "snapshot":
            rr = live.get(rid)
            if rr is not None:
                rr.snapshot = decode_swap_record(rec.get("record", {}))
    return RecoveryState(
        live=live,
        terminals=terminals,
        key_to_rid=key_to_rid,
        next_rid=next_rid,
        torn=torn,
        records=len(records),
    )


def readmit(scheduler, state: RecoveryState, decider=None):
    """Re-admit the recovered live set into a fresh scheduler with
    recompute cursors: each request re-enters as (prompt, committed)
    — `_admit` recomputes exactly that history, and deterministic
    greedy decode makes the resumed stream token-identical from the
    cursor. When a request carries a usable KV snapshot and `decider`
    (a `(cache, record, resume_len) -> bool` from
    `api.build_restore_decider`; None = always restore) prices the
    copy under the recompute, the snapshot rides `import_swap` and the
    swap-in admission path restores it with NO re-prefill.

    Returns (resubmitted, completed): `completed` are requests whose
    committed run already satisfied their stopping rule — finalizing
    them through the scheduler would emit a duplicate token, so they
    come back terminal for the front door to replay."""
    from flexflow_tpu.serving.scheduler import Request, RequestStatus

    resubmitted = []
    completed = []
    cache = getattr(scheduler, "cache", None)
    for rid in sorted(state.live):
        rr = state.live[rid]
        req = Request(
            rid=rr.rid,
            prompt=list(rr.prompt),
            max_new_tokens=rr.max_new_tokens,
            eos_token=rr.eos_token,
            # the original deadline's clock died with the old process;
            # re-arming it fresh would silently extend it, so recovery
            # drops it — the operator's journal keeps the recorded value
            deadline_s=None,
            tenant=rr.tenant,
            priority_class=rr.priority_class,
            adapter_id=rr.adapter_id,
            request_key=rr.key,
            generated=list(rr.committed),
        )
        if rr.complete:
            req.status = RequestStatus.FINISHED
            completed.append(req)
            continue
        snap = rr.snapshot
        if (
            snap is not None
            and cache is not None
            and int(snap.get("gen_len", -1)) == len(rr.committed)
        ):
            resume_len = len(rr.prompt) + len(rr.committed)
            try:
                use = decider is None or decider(cache, snap, resume_len)
                if use:
                    handle = cache.import_swap(dict(snap))
                    if handle is not None:
                        req.swap_handle = handle
            except ValueError:
                pass  # geometry mismatch: the recompute path still works
        scheduler.submit(req, strict=False)
        resubmitted.append(req)
    return resubmitted, completed

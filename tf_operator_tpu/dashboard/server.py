"""Threaded HTTP server exposing the store: REST API + static UI.

Routes (reference: dashboard/backend/handler/api_handler.go:74-113):

- GET    /api/tpujob                      — list jobs (?namespace=)
- POST   /api/tpujob                      — submit a job (JSON body)
- GET    /api/tpujob/{ns}/{name}          — job detail + processes + endpoints
- DELETE /api/tpujob/{ns}/{name}          — delete job (controller GCs children)
- GET    /api/tpujob/{ns}/{name}/trace    — the job's lifecycle trace as
  Chrome trace-event JSON (Perfetto-loadable; obs/export.py)
- GET    /api/tpujob/{ns}/{name}/telemetry — the job's live telemetry ring
  (per-rank step batches + gang summary + goodput decomposition)
- GET    /api/tpujob/{ns}/{name}/postmortem — the frozen hang/failure
  bundle + shipped per-rank stack dumps (404 LOUDLY when never frozen or
  GC'd with the job — never an empty tar)
- POST   /api/tpujob/{ns}/{name}/profile  — publish an on-demand profile
  directive (body: {"steps": N, "dir": path?}); the chief captures the
  next N steps and acks with a profile-capture span
- GET    /api/process/{ns}/{name}/logs    — process logs (kubelet-log analogue)
- GET    /api/events?namespace=           — events (the test oracle surface)
- GET    /api/namespaces                  — namespaces in use
- GET    /ui                              — single-page app (dashboard/ui.py):
  job list/detail with processes+logs+events, create form, events view —
  the reference React frontend's JobList/JobDetail/CreateJob surface
- GET    /healthz                         — liveness
- GET    /metrics                         — Prometheus text (when wired)

Generic object API (the remote-store seam; clients: runtime/remote_store.py):

- GET    /api/v1/{kind}?namespace=        — list raw objects of a kind
- POST   /api/v1/{kind}                   — create (body: serialized object)
- GET    /api/v1/{kind}/{ns}/{name}       — get
- PUT    /api/v1/{kind}/{ns}/{name}?check_version=1 — update (409 on stale)
- DELETE /api/v1/{kind}/{ns}/{name}       — delete
- GET    /api/v1/watch?kinds=A,B          — JSON-lines stream of watch
  events (existing objects replayed as ADDED first — list+watch contract)

Auth (utils.auth, r3): constructed with ``auth_token``, the server
requires ``Authorization: Bearer <token>`` on every mutating route and on
the whole /api/v1 surface (the machine seam); human read routes
(/ui, job reads, events, logs, /metrics, /healthz) stay open by default.
``auth_reads`` (r4, ``--auth-reads``) extends the same bearer to every
read route except /healthz — full reference parity, where Kubernetes
auth covers ALL API access (pkg/util/k8sutil/k8sutil.go:53-77) and the
dashboard talks to the authenticated apiserver
(dashboard/backend/client/manager.go:13-45).
"""

from __future__ import annotations

import json
import re
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional
from urllib.parse import parse_qs, unquote, urlparse

from tf_operator_tpu.api.types import (
    KIND_ENDPOINT,
    KIND_EVENT,
    KIND_PRIORITY_CLASS,
    KIND_PROCESS,
    KIND_QUEUE,
    KIND_TPUJOB,
    LABEL_JOB_NAME,
    TPUJob,
)
from tf_operator_tpu.api import set_defaults, validate_job, ValidationError
from tf_operator_tpu.api.validation import validate_priority_class, validate_queue
from tf_operator_tpu.api.types import _to_jsonable
from tf_operator_tpu.runtime.process_backend import LocalProcessControl
from tf_operator_tpu.runtime.serialize import KNOWN_KINDS, from_doc, to_doc
from tf_operator_tpu.runtime.store import (
    AlreadyExistsError,
    ConflictError,
    NotFoundError,
    Store,
)

from tf_operator_tpu.dashboard.ui import UI_HTML as _UI_HTML

_JOB_RE = re.compile(r"^/api/tpujob/([^/]+)/([^/]+)$")
_TRACE_RE = re.compile(r"^/api/tpujob/([^/]+)/([^/]+)/trace$")
_TELEMETRY_RE = re.compile(r"^/api/tpujob/([^/]+)/([^/]+)/telemetry$")
_PROFILE_RE = re.compile(r"^/api/tpujob/([^/]+)/([^/]+)/profile$")
_POSTMORTEM_RE = re.compile(r"^/api/tpujob/([^/]+)/([^/]+)/postmortem$")
_LOGS_RE = re.compile(r"^/api/process/([^/]+)/([^/]+)/logs$")
_OBJ_KIND_RE = re.compile(r"^/api/v1/([A-Za-z]+)$")
_OBJ_RE = re.compile(r"^/api/v1/([A-Za-z]+)/([^/]+)/([^/]+)$")


def _decode_segments(m):
    """Percent-decode matched path segments for the JOB routes, rejecting
    any whose decoded form is empty or contains '/' — job namespace/name
    pairs circulate as "ns/name" STRING keys (workqueue, expectations), so
    a %2F-smuggled slash would make distinct jobs collide there. Returns
    None → 400. The generic /api/v1 object routes deliberately stay
    permissive: the store keys on (kind, ns, name) TUPLES, so slashes in
    generic object names are unambiguous — and that round-trip is pinned
    by test_names_with_reserved_characters_round_trip."""
    segs = tuple(unquote(g) for g in m.groups())
    if any(not s or "/" in s for s in segs):
        return None
    return segs


class _Handler(BaseHTTPRequestHandler):
    server_version = "tpujob-dashboard/0.1"
    store: Store = None  # set by server factory
    metrics = None  # ControllerMetrics, set by server factory when wired
    ledger = None  # FleetLedger (obs/ledger.py), set by factory when wired
    watch_ping_interval: float = 15.0  # idle keep-alive period on watches
    auth_token: Optional[str] = None  # shared secret; None = open server
    auth_reads: bool = False  # r4 --auth-reads: bearer on EVERY route but /healthz

    # silence default request logging
    def log_message(self, fmt, *args):
        del fmt, args

    # -- helpers ----------------------------------------------------------

    def _authorized(self) -> bool:
        """Bearer-token check (utils.auth): mutating routes and the whole
        /api/v1 machine surface call this; no-op when no token is
        configured. On failure a 401 has already been written."""
        if self.auth_token is None:
            return True
        from tf_operator_tpu.utils.auth import check_bearer

        if check_bearer(self.headers.get("Authorization"), self.auth_token):
            return True
        self._error(401, "unauthorized")
        return False

    def _json(self, code: int, payload) -> None:
        body = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _error(self, code: int, message: str) -> None:
        self._json(code, {"error": message})

    def _job_payload(self, job: TPUJob, api_version: str = "") -> dict:
        if api_version == "v1alpha1":
            # v1alpha1-generation read surface: list-shaped replica specs +
            # the phase/state status block (v1alpha1/types.go:106-160).
            from tf_operator_tpu.api.v1alpha1 import to_v1alpha1

            return to_v1alpha1(job)
        d = job.to_dict()
        d["phase"] = job.status.phase().value
        return d

    # -- GET --------------------------------------------------------------

    def do_GET(self):  # noqa: N802 (stdlib casing)
        url = urlparse(self.path)
        q = parse_qs(url.query)
        ns = q.get("namespace", [None])[0]
        path = url.path

        if path == "/healthz":
            # liveness stays open even under --auth-reads: probes carry
            # no data and a dead-token probe loop would mask real outages
            return self._json(200, {"ok": True})
        # Full-surface auth (r4, --auth-reads): the reference rides
        # Kubernetes auth for EVERY API access, reads included
        # (/root/reference/pkg/util/k8sutil/k8sutil.go:53-77; the
        # dashboard talks to the authenticated apiserver,
        # dashboard/backend/client/manager.go:13-45). With auth_reads the
        # same bearer gates job reads, events, logs, /metrics and the UI
        # — training logs and eval metrics are not public data in the HA
        # topology this server advertises.
        if self.auth_reads and not self._authorized():
            return
        if path == "/metrics":
            if self.metrics is None:
                return self._error(404, "metrics not wired (no controller)")
            body = self.metrics.render().encode()
            self.send_response(200)
            self.send_header("Content-Type", "text/plain; version=0.0.4")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            return
        if path in ("/", "/ui"):
            body = _UI_HTML.encode()
            self.send_response(200)
            self.send_header("Content-Type", "text/html")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            return
        # ?api_version=v1alpha1 on job reads serves the older generation's
        # shape (list replica specs + phase/state status block).
        api_version = q.get("api_version", [""])[0]
        if path == "/api/tpujob":
            jobs = self.store.list(KIND_TPUJOB, namespace=ns)
            return self._json(
                200, {"items": [self._job_payload(j, api_version) for j in jobs]}
            )
        if path == "/api/namespaces":
            spaces = sorted({j.metadata.namespace for j in self.store.list(KIND_TPUJOB)})
            return self._json(200, {"items": spaces})
        if path == "/api/events":
            evs = self.store.list(KIND_EVENT, namespace=ns)
            return self._json(200, {"items": [_to_jsonable(e) for e in evs]})
        # Fleet ledger rollups (r18): computed from the durable record
        # set, not the store — they survive job GC and operator death.
        # Serialized with sort_keys so the acceptance's byte-identical
        # before/after-recovery comparison is about content, not dict
        # ordering.
        if path in ("/api/fleet/summary", "/api/fleet/hosts"):
            if self.ledger is None:
                return self._error(404, "fleet ledger not wired (--ledger-dir)")
            payload = (
                self.ledger.summary()
                if path == "/api/fleet/summary"
                else {"hosts": self.ledger.hosts()}
            )
            body = json.dumps(payload, sort_keys=True).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            return

        m = _TRACE_RE.match(path)
        if m:
            segs = _decode_segments(m)
            if segs is None:
                return self._error(400, "invalid name in path (empty or contains '/')")
            tns, tname = segs
            from tf_operator_tpu.obs.export import to_chrome_trace
            from tf_operator_tpu.obs.spans import job_trace

            try:
                job = self.store.get(KIND_TPUJOB, tns, tname)
            except NotFoundError:
                job = None
            spans = job_trace(self.store, tns, tname)
            if job is None and not spans:
                return self._error(404, f"no trace for tpujob {tns}/{tname}")
            return self._json(200, to_chrome_trace(spans, job=job))

        m = _TELEMETRY_RE.match(path)
        if m:
            segs = _decode_segments(m)
            if segs is None:
                return self._error(400, "invalid name in path (empty or contains '/')")
            tns, tname = segs
            from tf_operator_tpu.obs.spans import job_trace
            from tf_operator_tpu.obs.telemetry import (
                goodput_decomposition,
                job_telemetry,
                telemetry_summary,
            )

            try:
                job = self.store.get(KIND_TPUJOB, tns, tname)
            except NotFoundError:
                job = None
            batches = job_telemetry(self.store, tns, tname)
            if job is None and not batches:
                return self._error(404, f"no telemetry for tpujob {tns}/{tname}")
            spans = job_trace(self.store, tns, tname)
            submit = job.metadata.creation_timestamp if job else 0.0
            end = (job.status.completion_time if job else None) or time.time()
            return self._json(
                200,
                {
                    "job": f"{tns}/{tname}",
                    "batches": [to_doc(b) for b in batches],
                    "summary": telemetry_summary(batches),
                    "goodput": goodput_decomposition(spans, batches, submit, end),
                },
            )

        m = _POSTMORTEM_RE.match(path)
        if m:
            segs = _decode_segments(m)
            if segs is None:
                return self._error(400, "invalid name in path (empty or contains '/')")
            pns, pname = segs
            from tf_operator_tpu.obs.blackbox import (
                job_stackdumps,
                load_postmortem,
            )

            bundle = load_postmortem(self.store, pns, pname)
            if bundle is None:
                # LOUD by design: a GC'd job's forensics are gone with it,
                # and a live job without a bundle has nothing frozen yet —
                # neither case may read as an empty-but-successful result.
                try:
                    self.store.get(KIND_TPUJOB, pns, pname)
                    detail = "job exists but no postmortem has been frozen"
                except NotFoundError:
                    detail = (
                        "job deleted — forensics are GC'd with the job"
                    )
                return self._error(
                    404, f"no postmortem for tpujob {pns}/{pname} ({detail})"
                )
            dumps = job_stackdumps(self.store, pns, pname)
            return self._json(
                200,
                {
                    "job": f"{pns}/{pname}",
                    "reason": bundle.reason,
                    "frozen_at": bundle.time,
                    "truncated": bundle.truncated,
                    "bundle": bundle.payload,
                    "stackdumps": [
                        {
                            "rank": d.rank, "epoch": d.epoch,
                            "host": d.payload.get("host", ""),
                            "truncated": d.truncated,
                            "text": d.payload.get("text", ""),
                        }
                        for d in dumps
                    ],
                },
            )

        m = _JOB_RE.match(path)
        if m:
            # Path segments arrive percent-encoded (RemoteStore quotes
            # them); decode before they become store keys.
            segs = _decode_segments(m)
            if segs is None:
                return self._error(400, "invalid name in path (empty or contains '/')")
            ns, name = segs
            try:
                job = self.store.get(KIND_TPUJOB, ns, name)
            except NotFoundError:
                return self._error(404, f"tpujob {ns}/{name} not found")
            procs = self.store.list(
                KIND_PROCESS, namespace=ns, label_selector={LABEL_JOB_NAME: name}
            )
            eps = self.store.list(
                KIND_ENDPOINT, namespace=ns, label_selector={LABEL_JOB_NAME: name}
            )
            return self._json(
                200,
                {
                    "job": self._job_payload(job, api_version),
                    "processes": [_to_jsonable(p) for p in procs],
                    "endpoints": [_to_jsonable(e) for e in eps],
                },
            )

        # The generic object API (including the watch stream) is the
        # machine seam — all consumers are token-capable, so the whole
        # surface authenticates, reads included.
        if path.startswith("/api/v1/") and not self._authorized():
            return

        if path == "/api/v1/watch":
            kinds = [k for k in (q.get("kinds", [""])[0]).split(",") if k]
            bad = [k for k in kinds if k not in KNOWN_KINDS]
            if bad:
                return self._error(400, f"unknown kinds {bad}")
            return self._stream_watch(kinds or None)

        m = _OBJ_KIND_RE.match(path)
        if m:
            kind = m.group(1)
            if kind not in KNOWN_KINDS:
                return self._error(404, f"unknown kind {kind}")
            # ?label=k=v (repeatable): server-side selector so remote
            # consumers don't transfer the whole collection to filter it.
            selector = {}
            for pair in q.get("label", []):
                k, sep, v = pair.partition("=")
                if sep:
                    selector[k] = v
            items = self.store.list(
                kind, namespace=ns, label_selector=selector or None
            )
            return self._json(200, {"items": [to_doc(o) for o in items]})

        m = _OBJ_RE.match(path)
        if m:
            kind, ons, name = map(unquote, m.groups())
            if kind not in KNOWN_KINDS:
                return self._error(404, f"unknown kind {kind}")
            try:
                return self._json(200, to_doc(self.store.get(kind, ons, name)))
            except NotFoundError:
                return self._error(404, f"{kind} {ons}/{name} not found")

        m = _LOGS_RE.match(path)
        if m:
            segs = _decode_segments(m)
            if segs is None:
                return self._error(400, "invalid name in path (empty or contains '/')")
            ns, name = segs
            try:
                proc = self.store.get(KIND_PROCESS, ns, name)
            except NotFoundError:
                return self._error(404, f"process {ns}/{name} not found")
            log_path = proc.metadata.annotations.get(LocalProcessControl.LOG_ANNOTATION)
            if not log_path:
                return self._error(404, "no logs captured for this process")
            try:
                with open(log_path, "rb") as f:
                    # Tail the last 1MB without reading the whole file.
                    import os as _os

                    f.seek(0, _os.SEEK_END)
                    size = f.tell()
                    f.seek(max(0, size - 1024 * 1024))
                    data = f.read()
            except FileNotFoundError:
                # annotated when the process is created, opened when its child
                # is spawned: between the two nothing has been written yet
                data = b""
            except OSError as exc:
                return self._error(500, str(exc))
            self.send_response(200)
            self.send_header("Content-Type", "text/plain; charset=utf-8")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)
            return

        self._error(404, f"no route {path}")

    def _stream_watch(self, kinds) -> None:
        """Chunk the store's watch stream as JSON lines until the client
        disconnects. Existing objects replay as ADDED first (the store's
        list+watch contract), so a reconnecting agent reconverges.

        The watch is registered with the server so stop() can end it:
        otherwise server_close()'s handler-thread join would block forever
        on a stream whose client is idle."""
        with self._watch_lock:
            if self._watch_closed.is_set():
                return self._error(503, "server shutting down")
            w = self.store.watch(kinds=kinds)
            # Replay boundary: everything queued at watch creation is the
            # existing-object replay; a SYNCED marker after it lets remote
            # consumers reconcile away objects deleted while they were
            # disconnected (deletions are never replayed).
            replay_n = w.queue.qsize()
            self._active_watches.add(w)
        try:
            self.send_response(200)
            self.send_header("Content-Type", "application/x-ndjson")
            self.send_header("Cache-Control", "no-cache")
            self.end_headers()
            sent = 0
            if replay_n == 0:
                self.wfile.write(b'{"type": "SYNCED"}\n')
                self.wfile.flush()
            # Poll with a timeout instead of blocking forever: an idle
            # period writes a PING, so a silently-dead client (power loss,
            # no FIN) fails the write and this handler+watch get reaped
            # instead of leaking until the next real event.
            # Batched delivery: drain everything already queued and write
            # it as ONE buffered chunk with one flush — during a burst
            # (gang create, resync) the per-event write+flush syscalls
            # were the stream's dominant cost. The queue is also the
            # watch's backpressure bound: draining it promptly keeps the
            # store from closing this watch as overflowed.
            import queue as _queue

            stopped = False
            while not stopped:
                try:
                    ev = w.queue.get(timeout=self.watch_ping_interval)
                except Exception:
                    self.wfile.write(b'{"type": "PING"}\n')
                    self.wfile.flush()
                    continue
                chunk = bytearray()
                while True:
                    if ev is None:
                        stopped = True  # watch stopped; send what we have
                        break
                    chunk += json.dumps(
                        {"type": ev.type.value, "kind": ev.obj.kind, "object": to_doc(ev.obj)}
                    ).encode()
                    chunk += b"\n"
                    sent += 1
                    if sent == replay_n:
                        chunk += b'{"type": "SYNCED"}\n'
                    try:
                        ev = w.queue.get_nowait()
                    except _queue.Empty:
                        break
                if chunk:
                    self.wfile.write(chunk)
                    self.wfile.flush()
        except (BrokenPipeError, ConnectionResetError, OSError):
            pass  # client went away
        finally:
            w.stop()
            with self._watch_lock:
                self._active_watches.discard(w)

    def _read_body(self) -> dict:
        length = int(self.headers.get("Content-Length", 0))
        return json.loads(self.rfile.read(length) or b"{}")

    # -- POST / PUT / DELETE ----------------------------------------------

    def do_PUT(self):  # noqa: N802
        if not self._authorized():
            return
        url = urlparse(self.path)
        m = _OBJ_RE.match(url.path)
        if not m:
            return self._error(404, "PUT only at /api/v1/{kind}/{ns}/{name}")
        kind, ns, name = map(unquote, m.groups())
        if kind not in KNOWN_KINDS:
            return self._error(404, f"unknown kind {kind}")
        check = parse_qs(url.query).get("check_version", ["0"])[0] == "1"
        try:
            obj = from_doc(kind, self._read_body())
        except (ValueError, KeyError, TypeError) as exc:
            return self._error(400, f"invalid {kind}: {exc}")
        if (obj.metadata.namespace, obj.metadata.name) != (ns, name):
            return self._error(400, "body identity does not match URL")
        try:
            return self._json(200, to_doc(self.store.update(obj, check_version=check)))
        except NotFoundError:
            return self._error(404, f"{kind} {ns}/{name} not found")
        except ConflictError as exc:
            return self._json(409, {"error": str(exc), "code": "conflict"})

    def do_POST(self):  # noqa: N802
        if not self._authorized():
            return
        path = urlparse(self.path).path
        m = _OBJ_KIND_RE.match(path)
        if m:
            kind = m.group(1)
            if kind not in KNOWN_KINDS:
                return self._error(404, f"unknown kind {kind}")
            try:
                obj = from_doc(kind, self._read_body())
                if kind == KIND_TPUJOB:
                    # The generic path must not be a validation bypass:
                    # same defaulting + admission as the /api/tpujob route.
                    set_defaults(obj)
                    validate_job(obj)
                elif kind == KIND_QUEUE:
                    validate_queue(obj)
                elif kind == KIND_PRIORITY_CLASS:
                    validate_priority_class(obj)
            except (ValueError, ValidationError, KeyError, TypeError) as exc:
                return self._error(400, f"invalid {kind}: {exc}")
            try:
                return self._json(201, to_doc(self.store.create(obj)))
            except AlreadyExistsError as exc:
                return self._json(409, {"error": str(exc), "code": "already_exists"})
        m = _PROFILE_RE.match(path)
        if m:
            # On-demand profiling: bump the monotonic profile-directive
            # epoch on status (same protocol as resize_directive — the
            # chief observes the new epoch at its next flush boundary,
            # wraps N steps in profile_ctx, and acks completed_epoch).
            segs = _decode_segments(m)
            if segs is None:
                return self._error(400, "invalid name in path (empty or contains '/')")
            pns, pname = segs
            try:
                body = self._read_body()
            except (ValueError, TypeError) as exc:
                return self._error(400, f"invalid body: {exc}")
            try:
                steps = int(body.get("steps", 0))
            except (ValueError, TypeError):
                return self._error(400, "steps must be an integer")
            if steps <= 0:
                return self._error(400, "steps must be > 0")
            prof_dir = str(body.get("dir", "") or "")
            issued = {}

            def arm(job):
                cur = job.status.profile_directive or {}
                issued.clear()
                issued.update(
                    {
                        "epoch": int(cur.get("epoch", 0)) + 1,
                        "steps": steps,
                        "dir": prof_dir,
                        "time": time.time(),
                    }
                )
                job.status.profile_directive = dict(issued)

            if not self.store.update_with_retry(KIND_TPUJOB, pns, pname, arm):
                return self._error(404, f"tpujob {pns}/{pname} not found")
            return self._json(200, {"profile_directive": issued})
        if path != "/api/tpujob":
            return self._error(404, "POST only at /api/tpujob or /api/v1/{kind}")
        length = int(self.headers.get("Content-Length", 0))
        try:
            data = json.loads(self.rfile.read(length) or b"{}")
            # Dual API generations (SURVEY.md §0): list-based v1alpha1
            # documents are converted, map-based ones decode directly.
            from tf_operator_tpu.api.v1alpha1 import parse_job

            job = parse_job(data)
            set_defaults(job)
            validate_job(job)
        except (ValueError, ValidationError, KeyError, TypeError) as exc:
            return self._error(400, f"invalid job: {exc}")
        # Namespace auto-create semantics (api_handler.go:178-218) are
        # implicit: namespaces exist by use.
        try:
            created = self.store.create(job)
        except AlreadyExistsError as exc:
            return self._error(409, str(exc))
        self._json(201, self._job_payload(created))

    def do_DELETE(self):  # noqa: N802
        if not self._authorized():
            return
        path = urlparse(self.path).path
        m = _OBJ_RE.match(path)
        if m:
            kind, ns, name = map(unquote, m.groups())
            if kind not in KNOWN_KINDS:
                return self._error(404, f"unknown kind {kind}")
            try:
                self.store.delete(kind, ns, name)
            except NotFoundError:
                return self._error(404, f"{kind} {ns}/{name} not found")
            return self._json(200, {"deleted": f"{kind}/{ns}/{name}"})
        m = _JOB_RE.match(path)
        if not m:
            return self._error(404, "DELETE at /api/tpujob/{ns}/{name} or /api/v1/{kind}/{ns}/{name}")
        segs = _decode_segments(m)
        if segs is None:
            return self._error(400, "invalid name in path (empty or contains '/')")
        ns, name = segs
        try:
            self.store.delete(KIND_TPUJOB, ns, name)
        except NotFoundError:
            return self._error(404, f"tpujob {ns}/{name} not found")
        self._json(200, {"deleted": f"{ns}/{name}"})


class _BoundedThreadingHTTPServer(ThreadingHTTPServer):
    """ThreadingHTTPServer with a bounded handler-thread count.

    The stock server spawns one unbounded thread per connection — under a
    submit burst (500 sequential creates, plus pollers, plus long-lived
    watch streams) that is an unbounded thread population on the store's
    lock. ``max_workers`` caps concurrently-served connections; the
    accept loop blocks on the semaphore once saturated, which is
    backpressure on clients (their connects queue in the listen backlog)
    instead of memory/thread growth in the operator. Watch streams hold a
    permit for their lifetime — size the bound above the expected agent
    count (default 64 ≫ any tested topology)."""

    def __init__(self, addr, handler, max_workers: int = 64):
        self._permits = threading.BoundedSemaphore(max_workers)
        super().__init__(addr, handler)

    def process_request(self, request, client_address):
        self._permits.acquire()
        try:
            super().process_request(request, client_address)
        except BaseException:
            self._permits.release()
            raise

    def process_request_thread(self, request, client_address):
        try:
            super().process_request_thread(request, client_address)
        finally:
            self._permits.release()


class DashboardServer:
    def __init__(
        self,
        store: Store,
        host: str = "127.0.0.1",
        port: int = 8080,
        metrics=None,
        watch_ping_interval: float = 15.0,
        auth_token: Optional[str] = None,
        auth_reads: bool = False,
        max_workers: int = 64,
        ledger=None,
    ) -> None:
        """``auth_token``: shared secret (utils.auth) required on mutating
        routes and the /api/v1 surface; None serves anonymously (tests,
        localhost dev). ``auth_reads`` (r4): extend the bearer check to
        every read route except /healthz — reference-parity with
        Kubernetes auth covering all API access. Requesting auth_reads
        without a token is refused loudly (r5, ADVICE r4): silently
        serving an open server is the exact hole the flag exists to
        close — the CLI guard in cli/operator.py only covers CLI
        callers."""
        if auth_reads and not auth_token:
            raise ValueError(
                "auth_reads=True requires auth_token — without a token the "
                "server would serve every read anonymously"
            )
        self._watches: set = set()
        self._watch_closed = threading.Event()
        handler = type(
            "BoundHandler",
            (_Handler,),
            {
                "store": store,
                "metrics": metrics,
                "ledger": ledger,
                "watch_ping_interval": watch_ping_interval,
                "auth_token": auth_token,
                "auth_reads": bool(auth_reads),
                "_active_watches": self._watches,
                "_watch_lock": threading.Lock(),
                "_watch_closed": self._watch_closed,
            },
        )
        self.httpd = _BoundedThreadingHTTPServer(
            (host, port), handler, max_workers=max_workers
        )
        self._thread: Optional[threading.Thread] = None

    @property
    def port(self) -> int:
        return self.httpd.server_address[1]

    @property
    def url(self) -> str:
        host, port = self.httpd.server_address[:2]
        return f"http://{host}:{port}"

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self.httpd.serve_forever, name="dashboard", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        # End live watch streams first: server_close() joins handler
        # threads, and a stream whose client is idle never unblocks on
        # its own (the sentinel from Watch.stop() does). The closed flag
        # forecloses the register-after-snapshot race: registration under
        # the same lock refuses once set.
        self._watch_closed.set()
        for w in list(self._watches):
            w.stop()
        self.httpd.shutdown()
        self.httpd.server_close()
        if self._thread:
            self._thread.join(timeout=5)

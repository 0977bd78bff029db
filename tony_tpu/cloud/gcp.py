"""Cloud TPU queued-resources client — the concrete ``TpuApi`` the
coordinator's ``TpuVmBackend`` drives (tony_tpu/coordinator/backend.py).
This is the analogue of the reference really talking to its cluster: where
`TonyClient` submits through a live `YarnClient`
(TonyClient.java:369-424), this client creates/polls/deletes TPU slices
through the queued-resources REST surface and starts remote executors over
``gcloud compute tpus tpu-vm ssh``.

Seams (all injectable, all covered by recorded-response tests):

* ``HttpTransport`` — one ``request()`` method; default ``UrllibTransport``
  adds a Bearer token from ``default_token_provider`` (GCE/TPU-VM metadata
  server, falling back to ``gcloud auth print-access-token``).
* ``CommandRunner`` — starts/polls/kills the per-host remote executor
  command; default ``GcloudSshRunner`` shells out to gcloud (the SSH
  transport gcloud users already have configured). Tests inject a fake.

Slice naming: one queued resource per job type (``{app}-{job}``) holding
``num_slices`` nodes ``{name}-s{i}`` — multi-slice jobs are one atomic
request, matching the gang semantics the coordinator assumes.
"""

from __future__ import annotations

import json
import logging
import shlex
import subprocess
import threading
import time
import urllib.error
import urllib.request
from typing import Callable, Mapping, Protocol

log = logging.getLogger(__name__)

# Env keys matching any of these never ride the ssh argv (visible in
# process listings and the logged command prefix) — they go over stdin.
# Callers can also tag arbitrary keys via TONY_SECRET_ENV (comma-sep).
_SECRET_MARKERS = (
    "TOKEN", "SECRET", "KEY", "PASSWORD", "CREDENTIAL", "PASSPHRASE",
)


def _looks_secret(key: str, extra: frozenset[str] = frozenset()) -> bool:
    upper = key.upper()
    return key in extra or any(m in upper for m in _SECRET_MARKERS)


_TPU_API = "https://tpu.googleapis.com/v2alpha1"
_METADATA_TOKEN_URL = (
    "http://metadata.google.internal/computeMetadata/v1/instance/"
    "service-accounts/default/token"
)


class HttpTransport(Protocol):
    def request(
        self, method: str, url: str, body,
        headers: Mapping[str, str],
    ) -> tuple[int, bytes]:
        """Returns (status_code, response_body). ``body`` is bytes, None,
        or an open binary file (streamed uploads — callers then supply
        Content-Length). Error statuses are returned, not raised — callers
        decide what is fatal.

        Transports MAY additionally expose
        ``request_stream(method, url) -> (status, readable)`` for streamed
        downloads; GcsStorage uses it when present."""


class CommandRunner(Protocol):
    def start(
        self, node: str, worker: int, command: str,
        stdin_data: bytes | None = None,
    ) -> object:
        """Run ``command`` on ``worker`` of TPU-VM ``node``; returns a
        handle. ``stdin_data`` is piped to the remote command's stdin —
        the side channel for credentials that must stay out of argv."""

    def poll(self, handle: object) -> int | None:
        ...

    def kill(self, handle: object) -> None:
        ...


# ---------------------------------------------------------------------------
# Auth + default transport
# ---------------------------------------------------------------------------

def _metadata_token() -> tuple[str, float] | None:
    req = urllib.request.Request(
        _METADATA_TOKEN_URL, headers={"Metadata-Flavor": "Google"}
    )
    try:
        with urllib.request.urlopen(req, timeout=2) as resp:
            doc = json.loads(resp.read())
            # The metadata server serves a CACHED token until shortly
            # before expiry — expires_in is the real remaining life, which
            # can be far under the nominal 3600 s.
            return doc["access_token"], float(doc.get("expires_in", 3600))
    except Exception:
        return None


def _gcloud_token() -> tuple[str, float] | None:
    try:
        out = subprocess.run(
            ["gcloud", "auth", "print-access-token"],
            capture_output=True, text=True, timeout=30,
        )
    except (OSError, subprocess.TimeoutExpired):
        return None
    token = out.stdout.strip()
    if out.returncode == 0 and token:
        # gcloud does not report remaining life; assume a conservative
        # half of the nominal hour.
        return token, 1800.0
    return None


def default_token_provider() -> tuple[str, float]:
    """(access token, seconds of remaining life) for the Google APIs: the
    GCE/TPU-VM metadata server when running inside the cloud (the default
    service account — no key files on disk), else the operator's gcloud
    credentials."""
    got = _metadata_token() or _gcloud_token()
    if not got:
        raise RuntimeError(
            "no Google Cloud credentials: not on GCE (metadata server "
            "unreachable) and `gcloud auth print-access-token` failed — "
            "run `gcloud auth login` or supply a token_provider"
        )
    return got


class UrllibTransport:
    """stdlib HTTP with Bearer auth. Tokens are cached for their reported
    ``expires_in`` minus a safety margin (never a fixed window — the
    metadata server hands out the SAME cached token until shortly before
    expiry, so a fresh fetch can have minutes of life left), and a
    401/403 response drops the cache and retries once with a new token so
    a long-running coordinator survives token rollover."""

    _EXPIRY_MARGIN_S = 300.0

    def __init__(
        self, token_provider: Callable[[], str | tuple[str, float]] | None = None,
        timeout_s: float = 60.0,
    ) -> None:
        import threading

        self._provider = token_provider or default_token_provider
        self._timeout = timeout_s
        self._token: str | None = None
        self._token_expiry = 0.0  # monotonic deadline for the cached token
        # One transport is shared across threads (default_storage feeds
        # concurrent reader fetchers); the lock also collapses a refresh
        # stampede into one provider call.
        self._token_lock = threading.Lock()

    def _bearer(self) -> str:
        with self._token_lock:
            now = time.monotonic()
            if self._token is None or now >= self._token_expiry:
                got = self._provider()
                token, life = got if isinstance(got, tuple) else (got, 3600.0)
                self._token = token
                # Margin against clock skew / in-flight requests; even a
                # nearly-dead token is still cached briefly so a stuck
                # metadata server cannot be hammered in a poll loop.
                self._token_expiry = now + max(
                    life - self._EXPIRY_MARGIN_S, 30.0
                )
            return self._token

    def _drop_token(self) -> None:
        # Expire, don't clear: a concurrent _bearer() between the drop and
        # the refresh must see the old (possibly still valid) token, never
        # None — its own 401 retry covers the stale case.
        with self._token_lock:
            self._token_expiry = 0.0

    def request(
        self, method: str, url: str, body,
        headers: Mapping[str, str],
    ) -> tuple[int, bytes]:
        for attempt in (0, 1):
            hdrs = {"Authorization": f"Bearer {self._bearer()}", **headers}
            req = urllib.request.Request(
                url, data=body, headers=hdrs, method=method
            )
            try:
                with urllib.request.urlopen(req, timeout=self._timeout) as resp:
                    return resp.status, resp.read()
            except urllib.error.HTTPError as e:
                if e.code in (401, 403) and attempt == 0:
                    # Expired/rolled credentials, not a caller error:
                    # refresh once. (Streamed bodies cannot be replayed,
                    # but streamed uploads go through request() only with
                    # seekable files — rewind those.)
                    e.read()
                    self._drop_token()
                    if hasattr(body, "seek"):
                        body.seek(0)
                    continue
                return e.code, e.read()
        raise AssertionError("unreachable")

    def request_stream(self, method: str, url: str):
        """Streamed GET: returns (status, readable response). The caller
        owns closing the response (GcsStorage.download_file does)."""
        for attempt in (0, 1):
            req = urllib.request.Request(
                url, headers={"Authorization": f"Bearer {self._bearer()}"},
                method=method,
            )
            try:
                resp = urllib.request.urlopen(req, timeout=self._timeout)
                return resp.status, resp
            except urllib.error.HTTPError as e:
                if e.code in (401, 403) and attempt == 0:
                    e.read()
                    self._drop_token()
                    continue
                return e.code, e
        raise AssertionError("unreachable")


# ---------------------------------------------------------------------------
# Remote command runner
# ---------------------------------------------------------------------------

class GcloudSshRunner:
    """Remote executor lifecycle over ``gcloud compute tpus tpu-vm ssh``.
    The local ssh process mirrors the remote command: its exit code IS the
    executor's (ssh propagates it), so poll/kill are plain Popen calls."""

    def __init__(self, project: str, zone: str) -> None:
        self.project = project
        self.zone = zone

    def start(
        self, node: str, worker: int, command: str,
        stdin_data: bytes | None = None,
    ) -> subprocess.Popen:
        argv = [
            "gcloud", "compute", "tpus", "tpu-vm", "ssh", node,
            f"--project={self.project}", f"--zone={self.zone}",
            f"--worker={worker}", "--command", command,
        ]
        log.info("ssh %s worker %d: %s", node, worker, command[:120])
        proc = subprocess.Popen(
            argv, stdin=subprocess.PIPE if stdin_data is not None else None
        )
        if stdin_data is not None:
            assert proc.stdin is not None
            stdin = proc.stdin

            def feed() -> None:
                try:
                    stdin.write(stdin_data)
                    stdin.close()
                except (BrokenPipeError, OSError):
                    # gcloud died before draining stdin (bad zone, revoked
                    # auth). The handle's nonzero exit surfaces through
                    # poll() as a task failure — same as the secret-less
                    # path.
                    pass

            # Off-thread: a gcloud that stalls before draining stdin (or
            # secrets beyond the pipe buffer) must not wedge the
            # coordinator thread; the writer dies with the process.
            threading.Thread(
                target=feed, name=f"ssh-stdin-{node}-{worker}", daemon=True
            ).start()
        return proc

    def poll(self, handle: subprocess.Popen) -> int | None:
        return handle.poll()

    def kill(self, handle: subprocess.Popen) -> None:
        if handle.poll() is None:
            handle.kill()
            handle.wait()


# ---------------------------------------------------------------------------
# The TpuApi implementation
# ---------------------------------------------------------------------------

class GcpApiError(RuntimeError):
    def __init__(self, status: int, url: str, body: bytes) -> None:
        super().__init__(
            f"TPU API request failed with HTTP {status} for {url}: "
            f"{body[:300]!r}"
        )
        self.status = status


# Default TPU-VM runtime image per accelerator family (the published
# Cloud TPU software-version names): an empty runtime_version resolves
# against the accelerator being provisioned — a fixed v5e image would
# make every other generation unprovisionable with defaults.
_RUNTIME_BY_FAMILY = (
    ("v5litepod", "v2-alpha-tpuv5-lite"),
    ("v6e", "v2-alpha-tpuv6e"),
    ("v5p", "v2-alpha-tpuv5"),
    ("v4", "tpu-ubuntu2204-base"),
)


def default_runtime_version(accelerator_type: str) -> str:
    for prefix, runtime in _RUNTIME_BY_FAMILY:
        if accelerator_type.startswith(prefix):
            return runtime
    raise ValueError(
        f"no default runtime version for accelerator "
        f"{accelerator_type!r} — set tony.gcp.runtime-version"
    )


# queuedResources state -> the backend's 3-state model. Unlisted states
# (ACCEPTED, PROVISIONING, WAITING_FOR_RESOURCES, CREATING, ...) map to
# CREATING: still in flight.
_TERMINAL_STATES = {
    "ACTIVE": "READY",
    "FAILED": "FAILED",
    "SUSPENDED": "FAILED",
    "SUSPENDING": "FAILED",
}


class GcpQueuedResourceApi:
    """``TpuApi`` over the queued-resources REST surface.

    One queued resource per slice group; node ids ``{name}-s{i}``. The
    per-host executor start maps ``host_index`` onto (slice, worker) via
    the accelerator type's hosts-per-slice (SLICE_SHAPES), and runs
    ``bootstrap_command`` (default: ``python3 -m tony_tpu.cloud.bootstrap``
    — fetch the gs:// staged app dir, unzip, exec the executor).
    """

    def __init__(
        self,
        project: str,
        zone: str,
        *,
        runtime_version: str = "",
        transport: HttpTransport | None = None,
        runner: CommandRunner | None = None,
        python: str = "python3",
        network: str = "",
    ) -> None:
        self.project = project
        self.zone = zone
        self.runtime_version = runtime_version
        self.transport = transport or UrllibTransport()
        self.runner = runner or GcloudSshRunner(project, zone)
        self.python = python
        self.network = network
        # name -> (accelerator_type, num_slices, hosts_per_slice)
        self._groups: dict[str, tuple[str, int, int]] = {}

    # -- REST plumbing ------------------------------------------------------
    def _parent(self) -> str:
        return f"projects/{self.project}/locations/{self.zone}"

    def _call(
        self, method: str, path: str, payload: dict | None = None,
        ok: tuple[int, ...] = (200,),
    ) -> dict:
        url = f"{_TPU_API}/{path}"
        body = json.dumps(payload).encode() if payload is not None else None
        headers = {"Content-Type": "application/json"} if body else {}
        status, resp = self.transport.request(method, url, body, headers)
        if status not in ok:
            raise GcpApiError(status, url, resp)
        if not resp:
            return {}
        try:
            return json.loads(resp)
        except ValueError:
            # Tolerated non-JSON bodies (e.g. a 404 text on DELETE retry).
            return {}

    @staticmethod
    def _hosts_per_slice(accelerator_type: str) -> int:
        # Deferred: a module-level import here closes the cycle
        # history -> writer -> cloud -> gcp -> coordinator -> history,
        # breaking any entry point that imports tony_tpu.history first
        # (e.g. ``python -m tony_tpu.history.server``).
        from tony_tpu.coordinator.backend import SLICE_SHAPES

        for shapes in SLICE_SHAPES.values():
            for accel, hosts in shapes.values():
                if accel == accelerator_type:
                    return hosts
        raise ValueError(f"unknown accelerator type {accelerator_type!r}")

    # -- TpuApi -------------------------------------------------------------
    def create_slice(
        self, name: str, accelerator_type: str, num_slices: int
    ) -> None:
        hosts = self._hosts_per_slice(accelerator_type)
        # Field names use the canonical proto-JSON camelCase form — the
        # same spelling the API emits in responses (start_executor reads
        # `tpu.nodeSpec[].node.acceleratorType` back from a GET). The
        # endpoint's lenient JSON accepts snake_case on writes too, but
        # one spelling on both sides keeps requests diffable against
        # recorded responses.
        node = {
            "acceleratorType": accelerator_type,
            "runtimeVersion": (
                self.runtime_version
                or default_runtime_version(accelerator_type)
            ),
        }
        if self.network:
            node["networkConfig"] = {"network": self.network}
        spec = {
            "tpu": {
                "nodeSpec": [
                    {
                        "parent": self._parent(),
                        "nodeId": f"{name}-s{i}",
                        "node": node,
                    }
                    for i in range(num_slices)
                ]
            }
        }
        self._call(
            "POST",
            f"{self._parent()}/queuedResources?queued_resource_id={name}",
            spec,
        )
        self._groups[name] = (accelerator_type, num_slices, hosts)
        log.info(
            "queued %d x %s as %s", num_slices, accelerator_type, name
        )

    def slice_state(self, name: str) -> str:
        doc = self._call(
            "GET", f"{self._parent()}/queuedResources/{name}"
        )
        raw = doc.get("state", {}).get("state", "CREATING")
        return _TERMINAL_STATES.get(raw, "CREATING")

    def start_executor(
        self, name: str, host_index: int, env: Mapping[str, str]
    ) -> object:
        if name not in self._groups:
            # A coordinator restarted mid-flight re-learns the group shape
            # from the API instead of failing.
            doc = self._call(
                "GET", f"{self._parent()}/queuedResources/{name}"
            )
            specs = doc.get("tpu", {}).get("nodeSpec", [])
            accel = (
                specs[0].get("node", {}).get("acceleratorType", "")
                if specs else ""
            )
            if not accel:
                raise RuntimeError(
                    f"queued resource {name} reports no node specs — "
                    f"cannot infer its slice shape to place host "
                    f"{host_index}; re-poll once the resource materializes"
                )
            self._groups[name] = (
                accel, len(specs), self._hosts_per_slice(accel)
            )
        _, _, hosts = self._groups[name]
        slice_idx, worker = divmod(host_index, hosts)
        node = f"{name}-s{slice_idx}"
        # Credentials must not ride the ssh argv: command lines are visible
        # in process listings on both the client host and the TPU VM, and
        # the command prefix is logged. Secret-looking env is piped through
        # the remote shell's stdin (one value per line, read before exec)
        # so only the NAMES appear in argv/logs.
        tagged = frozenset(
            k.strip()
            for k in str(env.get("TONY_SECRET_ENV", "")).split(",")
            if k.strip()
        )
        secret_keys = sorted(
            k for k in env
            if k != "TONY_SECRET_ENV" and _looks_secret(k, tagged)
        )
        for k in secret_keys:
            if "\n" in str(env[k]):
                # The stdin protocol is one value per line; an embedded
                # newline would silently shift every later binding.
                raise ValueError(
                    f"secret env {k} contains a newline — cannot deliver "
                    f"over the line-oriented ssh stdin channel"
                )
        plain = {k: v for k, v in env.items() if k not in secret_keys}
        exports = " ".join(
            f"export {k}={shlex.quote(str(v))};" for k, v in sorted(plain.items())
        )
        reads = " ".join(
            f"IFS= read -r {k}; export {k};" for k in secret_keys
        )
        stdin_data = (
            ("".join(f"{env[k]}\n" for k in secret_keys)).encode()
            if secret_keys else None
        )
        staged = env.get("TONY_STAGED_URI", "")
        # Stage-0 loader is inlined (stdlib-only): a bare TPU VM has no
        # tony_tpu to ``-m`` into; the loader fetches the staged framework
        # copy first (see cloud.bootstrap.INLINE_LOADER).
        from tony_tpu.cloud.bootstrap import INLINE_LOADER

        command = (
            f"{reads} {exports} exec {self.python} -c "
            f"{shlex.quote(INLINE_LOADER)} {shlex.quote(staged)}"
        )
        return self.runner.start(node, worker, command, stdin_data)

    def executor_status(self, handle: object) -> int | None:
        return self.runner.poll(handle)

    def kill_executor(self, handle: object) -> None:
        self.runner.kill(handle)

    def list_queued_resources(self, prefix: str = "") -> list[dict]:
        """All queued resources in the zone (paged), optionally filtered
        by resource-id prefix. Returns ``[{"name": short_id, "state":
        STATE, "nodes": n}, ...]``.

        This is the janitor's discovery half: slice
        names are deterministic ``{app}-{job}``, so a SECOND process can
        find — and ``delete_slice`` — the groups a crashed coordinator
        leaked. The reference inherited this protection from YARN (the RM
        reaps an expired AM's containers, TonyApplicationMaster.java's
        liveness model); on TPU VMs nothing reaps queued resources, so
        the capability must be explicit."""
        out: list[dict] = []
        page = ""
        while True:
            path = f"{self._parent()}/queuedResources"
            if page:
                import urllib.parse

                # Page tokens are base64-ish ('+'/'=' would corrupt an
                # unencoded query string) — same rule as the GCS lister.
                path += f"?pageToken={urllib.parse.quote(page, safe='')}"
            doc = self._call("GET", path)
            for item in doc.get("queuedResources", []):
                short = item.get("name", "").rsplit("/", 1)[-1]
                if prefix and not short.startswith(prefix):
                    continue
                state = item.get("state", {})
                out.append({
                    "name": short,
                    "state": (state.get("state", "UNKNOWN")
                              if isinstance(state, dict) else str(state)),
                    "nodes": len(
                        item.get("tpu", {}).get("nodeSpec", [])
                    ),
                })
            page = doc.get("nextPageToken", "")
            if not page:
                return out

    def delete_slice(self, name: str) -> None:
        # force: tear down even with nodes still attached — session teardown
        # must not wedge on a half-provisioned group.
        self._call(
            "DELETE",
            f"{self._parent()}/queuedResources/{name}?force=true",
            ok=(200, 404),
        )
        self._groups.pop(name, None)

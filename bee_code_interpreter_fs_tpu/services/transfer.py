"""Delta-based workspace transfer state: per-host manifests + accounting.

The control plane's half of the workspace-sync protocol. Storage names every
object by its content SHA-256 (services/storage.py) and the executor server
keeps a per-workspace ``rel -> sha256`` manifest (executor/server.cpp), so
both sides speak the same identifier and file bytes only ever move when the
content is genuinely new to the receiver:

- **Upload delta** — a path whose ``(rel, sha)`` already matches the host's
  manifest is skipped outright (no HTTP at all); a session turn with N
  unchanged input files moves O(1) bytes instead of O(total bytes x hosts).
- **Hash-negotiated download** — a changed file whose server-reported sha
  already ``exists()`` in storage records the mapping and moves no bytes.
- **Old-binary fallback** — a host that answers without hashes (plain-string
  ``files`` array, 404 on ``/workspace-manifest``) is remembered as legacy
  and gets exactly the pre-manifest behavior: full uploads, full downloads.
- **Copy from storage** — a host whose backend declared that it sees the
  storage directory (``Sandbox.meta["shares_storage"]``: the local backend)
  copies an input file object -> workspace itself, inside the kernel; the
  bytes never cross the control plane. A host that refuses the route once
  keeps the streamed PUT from then on.

State lives in ``Sandbox.meta["transfer"]`` so it travels with the sandbox
through the pool; generation turnover (``/reset``) wipes the workspace, so
the executor clears it back to empty-known at that point (see
``CodeExecutor._turnover``).

Known staleness window, accepted by design: a user daemon that survives a
SUCCESSFUL execute (the group kill only fires on timeout/crash) can mutate a
workspace file after the post-execute scan; the next turn's blind skip then
trusts a manifest entry the daemon invalidated, so that turn runs against
the mutated input. Mutations by the user code itself are safe (the scan
reports them and the cache updates), runner kills invalidate + resync, and
the server's conditional-PUT path re-checks the on-disk signature — only
the zero-request skip has no guard, and giving it one would cost the very
round trip the delta exists to remove. Sessions whose user code leaves
daemons behind mutate their own inputs at their own risk.
"""

from __future__ import annotations

from dataclasses import dataclass

from ..utils.validation import SHA256_HEX_RE


def parse_files_field(raw) -> tuple[list[tuple[str, str | None]], bool]:
    """Decode an execute response's ``files`` array into ``(rel, sha|None)``
    pairs plus a has-hashes verdict.

    New binaries send ``[{"path": rel, "sha256": sha}, ...]`` (sha may be
    absent for a file that vanished mid-scan); old binaries send plain
    strings. Any string entry marks the response hash-less (``False``) — the
    caller must fall back to full transfers for that host. An empty array is
    NOT evidence either way and reports ``True``.
    """
    entries: list[tuple[str, str | None]] = []
    has_hashes = True
    for item in raw or []:
        if isinstance(item, str):
            entries.append((item, None))
            has_hashes = False
        elif isinstance(item, dict):
            rel = item.get("path")
            if not isinstance(rel, str) or not rel:
                continue
            sha = item.get("sha256")
            if not (isinstance(sha, str) and SHA256_HEX_RE.match(sha)):
                sha = None
            entries.append((rel, sha))
    return entries, has_hashes


def compute_upload_delta(
    manifest: dict[str, str] | None, uploads: dict[str, str]
) -> tuple[dict[str, str], dict[str, str]]:
    """Split ``{rel: object_id}`` into (to_upload, skipped) against a host
    manifest. Skippable = the manifest is known AND already maps ``rel`` to
    exactly this object id AND the id is a real content sha (legacy opaque
    ids can't be negotiated — they always upload). ``manifest=None`` means
    the host's workspace state is unknown: upload everything."""
    if manifest is None:
        return dict(uploads), {}
    to_upload: dict[str, str] = {}
    skipped: dict[str, str] = {}
    for rel, object_id in uploads.items():
        if SHA256_HEX_RE.match(object_id) and manifest.get(rel) == object_id:
            skipped[rel] = object_id
        else:
            to_upload[rel] = object_id
    return to_upload, skipped


class HostManifest:
    """What the control plane believes one host's workspace contains.

    ``entries`` is ``rel -> sha256`` or ``None`` (= unknown; full uploads
    until a resync succeeds). ``supports`` is a tri-state memo of whether the
    host speaks the manifest protocol: ``None`` until observed, ``True``
    after any hashed response, ``False`` once a response proves it legacy —
    after which no resync is ever attempted again (the endpoint would 404
    on every execute). ``copies`` says the host's server can copy an input
    file from the storage directory itself: what the sandbox's backend
    declared, until the host refuses the route once."""

    __slots__ = ("entries", "supports", "disabled", "copies")

    def __init__(self, disabled: bool = False, copies: bool = False) -> None:
        # Seeded empty-KNOWN: a sandbox's workspace starts empty at spawn,
        # and reset() restores this same state after a workspace wipe.
        self.entries: dict[str, str] | None = {}
        self.supports: bool | None = None
        # Hard off (config kill switch): permanently legacy — no state
        # updates may ever resurrect negotiation for this host.
        self.disabled = disabled
        self.copies = copies
        if disabled:
            self.mark_legacy()

    def delta(self, uploads: dict[str, str]) -> tuple[dict[str, str], dict[str, str]]:
        return compute_upload_delta(self.entries, uploads)

    def record_upload(self, rel: str, sha: str | None) -> None:
        """A PUT for `rel` succeeded. A response carrying the server-computed
        sha confirms manifest support; one without (old binary) proves the
        host legacy."""
        if self.disabled:
            return
        if sha is not None and SHA256_HEX_RE.match(sha):
            self.supports = True
            if self.entries is not None:
                self.entries[rel] = sha
        else:
            self.mark_legacy()

    def apply_execute_response(
        self, entries: list[tuple[str, str | None]], deleted: list[str]
    ) -> None:
        """Fold one host's execute response into the cache: changed files
        take their fresh sha (a hash-less entry — file vanished mid-scan —
        just drops from the cache), deleted files leave it."""
        if self.entries is None:
            return
        for rel, sha in entries:
            if sha is not None:
                self.entries[rel] = sha
            else:
                self.entries.pop(rel, None)
        for rel in deleted:
            if isinstance(rel, str):
                self.entries.pop(rel, None)

    def invalidate(self) -> None:
        """Workspace state is no longer trustworthy (the host's runner was
        killed mid-request): forget everything, keep the protocol memo. The
        next upload phase resyncs from GET /workspace-manifest."""
        self.entries = None

    def mark_legacy(self) -> None:
        """The host answered without hashes: it is an old binary. Behave
        exactly as the pre-manifest control plane did, permanently."""
        self.entries = None
        self.supports = False

    def resynced(self, entries: dict[str, str]) -> None:
        if self.disabled:
            return
        self.entries = dict(entries)
        self.supports = True

    def reset(self) -> None:
        """Generation turnover wiped the workspace: back to empty-known."""
        if not self.disabled:
            self.entries = {}


class SandboxTransfer:
    """Per-sandbox transfer state: one HostManifest per host URL.

    ``enabled=False`` (config kill switch) pins every host to the legacy
    full-transfer path without touching the wire protocol.
    ``shares_storage`` is the backend's declaration that the sandbox's hosts
    see the storage directory (see ``HostManifest.copies``)."""

    def __init__(self, enabled: bool = True, shares_storage: bool = False) -> None:
        self.enabled = enabled
        self.shares_storage = shares_storage
        self._hosts: dict[str, HostManifest] = {}

    def host(self, base_url: str) -> HostManifest:
        manifest = self._hosts.get(base_url)
        if manifest is None:
            manifest = HostManifest(
                disabled=not self.enabled, copies=self.shares_storage
            )
            self._hosts[base_url] = manifest
        return manifest

    def invalidate(self) -> None:
        for manifest in self._hosts.values():
            manifest.invalidate()

    def reset(self) -> None:
        for manifest in self._hosts.values():
            manifest.reset()


@dataclass
class TransferStats:
    """Byte/file movement of one Execute's upload+download phases."""

    upload_bytes: int = 0
    upload_files: int = 0
    # Of those, what the sandbox's own server copied from the storage
    # directory: counted in upload_bytes / upload_files too.
    upload_copied_bytes: int = 0
    upload_copied_files: int = 0
    upload_skipped_bytes: int = 0
    upload_skipped_files: int = 0
    download_bytes: int = 0
    download_files: int = 0
    download_skipped_bytes: int = 0
    download_skipped_files: int = 0

    def as_phases(self) -> dict[str, float]:
        """Byte counters merged into Result.phases (floats, like the phase
        timings, so both API surfaces carry them unchanged)."""
        return {
            "upload_bytes": float(self.upload_bytes),
            "upload_copied_bytes": float(self.upload_copied_bytes),
            "upload_skipped_bytes": float(self.upload_skipped_bytes),
            "download_bytes": float(self.download_bytes),
            "download_skipped_bytes": float(self.download_skipped_bytes),
        }

    def emit(self, metrics) -> None:
        """Feed the transfer metric family (duck-typed: tests pass a stub)."""
        transferred = getattr(metrics, "transfer_bytes", None)
        if transferred is None:
            return
        metrics.transfer_bytes.inc(self.upload_bytes, direction="upload")
        metrics.transfer_bytes.inc(self.download_bytes, direction="download")
        metrics.transfer_files.inc(self.upload_files, direction="upload")
        metrics.transfer_files.inc(self.download_files, direction="download")
        metrics.transfer_copied_bytes.inc(self.upload_copied_bytes)
        metrics.transfer_copied_files.inc(self.upload_copied_files)
        metrics.transfer_skipped_bytes.inc(
            self.upload_skipped_bytes, direction="upload"
        )
        metrics.transfer_skipped_bytes.inc(
            self.download_skipped_bytes, direction="download"
        )
        metrics.transfer_skipped_files.inc(
            self.upload_skipped_files, direction="upload"
        )
        metrics.transfer_skipped_files.inc(
            self.download_skipped_files, direction="download"
        )
        metrics.transfer_phase_bytes.observe(
            float(self.upload_bytes), phase="upload"
        )
        metrics.transfer_phase_bytes.observe(
            float(self.download_bytes), phase="download"
        )

"""Pure unit coverage for the delta-transfer state machine
(services/transfer.py): upload-delta computation, execute-response parsing,
host-manifest lifecycle transitions, and the stats accounting the metrics
and Result.phases surfaces consume.
"""

from bee_code_interpreter_fs_tpu.services.transfer import (
    HostManifest,
    SandboxTransfer,
    TransferStats,
    compute_upload_delta,
    parse_files_field,
)

SHA_A = "a" * 64
SHA_B = "b" * 64
SHA_C = "c" * 64


# ------------------------------------------------------------- upload delta


def test_delta_skips_exact_matches_only():
    manifest = {"kept.txt": SHA_A, "changed.txt": SHA_B}
    uploads = {
        "kept.txt": SHA_A,  # same rel, same sha -> skip
        "changed.txt": SHA_C,  # same rel, different sha -> upload
        "new.txt": SHA_B,  # same sha exists under ANOTHER rel -> upload
    }
    to_upload, skipped = compute_upload_delta(manifest, uploads)
    assert skipped == {"kept.txt": SHA_A}
    assert to_upload == {"changed.txt": SHA_C, "new.txt": SHA_B}


def test_delta_unknown_manifest_uploads_everything():
    to_upload, skipped = compute_upload_delta(None, {"a.txt": SHA_A})
    assert to_upload == {"a.txt": SHA_A}
    assert skipped == {}


def test_delta_legacy_object_ids_never_skip():
    # A legacy opaque id is not a content sha: it can never be negotiated,
    # even if a stale manifest entry happens to carry the same string.
    manifest = {"a.txt": "legacy-id-1"}
    to_upload, skipped = compute_upload_delta(manifest, {"a.txt": "legacy-id-1"})
    assert to_upload == {"a.txt": "legacy-id-1"}
    assert skipped == {}


def test_delta_empty_known_manifest_uploads_everything():
    to_upload, skipped = compute_upload_delta({}, {"a.txt": SHA_A})
    assert to_upload == {"a.txt": SHA_A}
    assert skipped == {}


# --------------------------------------------------------- response parsing


def test_parse_files_field_hashed_entries():
    entries, has_hashes = parse_files_field(
        [{"path": "a.txt", "sha256": SHA_A}, {"path": "b.txt"}]
    )
    assert entries == [("a.txt", SHA_A), ("b.txt", None)]
    assert has_hashes is True  # a missing sha on one entry is not legacy


def test_parse_files_field_legacy_strings():
    entries, has_hashes = parse_files_field(["a.txt", "b.txt"])
    assert entries == [("a.txt", None), ("b.txt", None)]
    assert has_hashes is False


def test_parse_files_field_empty_is_not_evidence():
    entries, has_hashes = parse_files_field([])
    assert entries == []
    assert has_hashes is True


def test_parse_files_field_rejects_malformed_shas():
    entries, _ = parse_files_field(
        [{"path": "a.txt", "sha256": "NOT-A-SHA"}, {"sha256": SHA_A}]
    )
    # Bad sha -> entry kept hash-less; entry without a path dropped.
    assert entries == [("a.txt", None)]


# --------------------------------------------------- host manifest lifecycle


def test_manifest_starts_empty_known_and_records_uploads():
    manifest = HostManifest()
    assert manifest.entries == {}
    manifest.record_upload("a.txt", SHA_A)
    assert manifest.entries == {"a.txt": SHA_A}
    assert manifest.supports is True


def test_manifest_hashless_upload_response_marks_legacy():
    manifest = HostManifest()
    manifest.record_upload("a.txt", None)
    assert manifest.entries is None
    assert manifest.supports is False
    # Legacy is sticky: later uploads change nothing and delta never skips.
    to_upload, skipped = manifest.delta({"a.txt": SHA_A})
    assert to_upload and not skipped


def test_manifest_execute_response_updates_and_deletes():
    manifest = HostManifest()
    manifest.record_upload("a.txt", SHA_A)
    manifest.record_upload("b.txt", SHA_B)
    manifest.apply_execute_response([("a.txt", SHA_C)], deleted=["b.txt"])
    assert manifest.entries == {"a.txt": SHA_C}
    # A hash-less entry (file vanished mid-scan) drops from the cache so the
    # next turn re-uploads rather than wrongly skipping.
    manifest.apply_execute_response([("a.txt", None)], deleted=[])
    assert manifest.entries == {}


def test_manifest_invalidate_then_resync():
    manifest = HostManifest()
    manifest.record_upload("a.txt", SHA_A)
    manifest.invalidate()
    assert manifest.entries is None
    assert manifest.supports is True  # protocol memo survives doubt
    manifest.resynced({"a.txt": SHA_B})
    assert manifest.entries == {"a.txt": SHA_B}


def test_manifest_reset_restores_empty_known():
    manifest = HostManifest()
    manifest.record_upload("a.txt", SHA_A)
    manifest.reset()
    assert manifest.entries == {}
    assert manifest.supports is True


def test_sandbox_transfer_disabled_pins_legacy():
    transfer = SandboxTransfer(enabled=False)
    manifest = transfer.host("http://h0")
    assert manifest.supports is False
    assert manifest.entries is None


def test_sandbox_transfer_reset_covers_all_hosts():
    transfer = SandboxTransfer()
    transfer.host("http://h0").record_upload("a.txt", SHA_A)
    transfer.host("http://h1").record_upload("a.txt", SHA_A)
    transfer.reset()
    assert transfer.host("http://h0").entries == {}
    assert transfer.host("http://h1").entries == {}


# ------------------------------------------------------------------- stats


def test_stats_phases_blob():
    stats = TransferStats(
        upload_bytes=10,
        upload_copied_bytes=5,
        upload_skipped_bytes=20,
        download_bytes=30,
        download_skipped_bytes=40,
    )
    assert stats.as_phases() == {
        "upload_bytes": 10.0,
        "upload_copied_bytes": 5.0,
        "upload_skipped_bytes": 20.0,
        "download_bytes": 30.0,
        "download_skipped_bytes": 40.0,
    }


def test_stats_emit_feeds_transfer_metrics():
    from bee_code_interpreter_fs_tpu.utils.metrics import ExecutorMetrics

    metrics = ExecutorMetrics()
    TransferStats(
        upload_bytes=100,
        upload_files=2,
        upload_skipped_bytes=50,
        upload_skipped_files=1,
        download_bytes=7,
        download_files=1,
    ).emit(metrics)
    rendered = metrics.registry.render()
    assert (
        'code_interpreter_transfer_bytes_total{direction="upload"} 100'
        in rendered
    )
    assert (
        'code_interpreter_transfer_skipped_bytes_total{direction="upload"} 50'
        in rendered
    )
    assert (
        'code_interpreter_transfer_files_total{direction="download"} 1'
        in rendered
    )
    assert "code_interpreter_transfer_phase_bytes_bucket" in rendered

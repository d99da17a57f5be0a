"""Ratchets on what an owner can set (ROADMAP C1): the ``DISQ_TPU_*``
names the package reads, the fields of ``DisqOptions``, and what a
write's staging fingerprint is made of. A PR that adds a name or a
field changes the pin here, and so says that it did."""

import dataclasses
import json
import os
import re

from bam_oracle import DEFAULT_REFS, make_bam_bytes, synth_records
from disq_tpu import DisqOptions, ReadsStorage, VariantsStorage

PACKAGE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "disq_tpu")

# ``grep -rhoE 'DISQ_TPU_[A-Z0-9_]+' disq_tpu --include=*.py | sort -u``:
# 31 names, and ``DISQ_TPU_DEVICE_`` from ``util.py``'s message about
# the ``DISQ_TPU_DEVICE_*`` family
ENV_NAMES = (
    "DISQ_TPU_CRAM_CORE", "DISQ_TPU_CRAM_RANS_O1", "DISQ_TPU_DEBUG",
    "DISQ_TPU_DEVICE_", "DISQ_TPU_DEVICE_INFLATE", "DISQ_TPU_DEVICE_RANS",
    "DISQ_TPU_DEVICE_SERVICE", "DISQ_TPU_DISPATCH_HBM_MB",
    "DISQ_TPU_DISPATCH_WINDOW", "DISQ_TPU_HTTP_CACHE_BLOCKS",
    "DISQ_TPU_INTROSPECT_PORT", "DISQ_TPU_MESH", "DISQ_TPU_POSTMORTEM_DIR",
    "DISQ_TPU_PROCESS_COUNT", "DISQ_TPU_PROCESS_ID", "DISQ_TPU_PROFILE_HZ",
    "DISQ_TPU_READ_FILTER", "DISQ_TPU_RESIDENT_DECODE", "DISQ_TPU_SCHED",
    "DISQ_TPU_SCHED_FAILOVER", "DISQ_TPU_SCHED_HOST",
    "DISQ_TPU_SCHED_LEASE_N", "DISQ_TPU_SCHED_LEASE_S",
    "DISQ_TPU_SCHED_SALT", "DISQ_TPU_SCHED_STATIC", "DISQ_TPU_SCHED_STEAL",
    "DISQ_TPU_SCHED_WEIGHT", "DISQ_TPU_SERVICE_FLUSH_MS", "DISQ_TPU_SLO",
    "DISQ_TPU_TRACE_DIR", "DISQ_TPU_TRACE_JSONL", "DISQ_TPU_TRACE_REQUESTS",
)


def test_the_env_names_the_package_reads_are_the_pinned_ones():
    found = set()
    for dirpath, _dirs, files in os.walk(PACKAGE):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(dirpath, name)) as f:
                    found.update(re.findall(r"DISQ_TPU_[A-Z0-9_]+", f.read()))
    assert len(ENV_NAMES) == 32
    assert sorted(found) == sorted(ENV_NAMES)


def test_the_options_are_34_fields_and_no_storage_arms_a_device_deflate():
    assert len(dataclasses.fields(DisqOptions)) == 34
    for storage in (ReadsStorage, VariantsStorage):
        assert not hasattr(storage, "device_deflate")


def test_a_manifest_of_the_fork_s_fingerprint_is_started_fresh(tmp_path):
    """A staging directory left by a crash under a tree whose write
    fingerprint still held ``"device_deflate": false`` matches no
    write of this tree: the resumed save adopts none of its parts."""
    from disq_tpu.api import (
        BaiWriteOption,
        SbiWriteOption,
        StageManifestWriteOption,
    )
    from disq_tpu.bam.sink import _batch_digest
    from disq_tpu.runtime import StageManifest

    src = str(tmp_path / "in.bam")
    with open(src, "wb") as f:
        f.write(make_bam_bytes(
            DEFAULT_REFS, synth_records(400, seed=3, sorted_coord=True),
            blocksize=600, sort_order="coordinate"))
    storage = ReadsStorage.make_default().num_shards(4)
    ds = storage.read(src)
    out, mpath = str(tmp_path / "out.bam"), str(tmp_path / "write.manifest")
    os.makedirs(out + ".parts")
    left = StageManifest(mpath, params={
        "target": out, "records": ds.count(),
        "digest": _batch_digest(ds.reads), "n_shards": 4,
        "bai": True, "sbi": True, "device_deflate": False})
    for k in range(4):
        part = os.path.join(out + ".parts", f"part-{k:05d}")
        with open(part, "wb") as f:
            f.write(b"not a part")
        left.mark_done("bam.parts", k, {
            "part": part, "len": 10, "sbi": None, "bai": None})
    with open(mpath) as f:
        assert len(json.load(f)["stages"]["bam.parts"]["shards"]) == 4

    indexes = (BaiWriteOption.ENABLE, SbiWriteOption.ENABLE)
    storage.write(ds, out, StageManifestWriteOption(mpath), *indexes)
    clean = str(tmp_path / "clean.bam")
    storage.write(ds, clean, *indexes)
    for ext in ("", ".bai", ".sbi"):
        with open(out + ext, "rb") as got, open(clean + ext, "rb") as want:
            assert got.read() == want.read(), ext
    assert not os.path.exists(mpath) and not os.path.exists(out + ".parts")

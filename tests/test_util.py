"""Unit tests for util + marker + chip claiming."""

import os
import re

import pytest

from tensorflowonspark_tpu import chip_info, marker, util


def test_marker_hierarchy():
    assert isinstance(marker.EndPartition(), marker.Marker)
    assert not isinstance(marker.Marker(), marker.EndPartition)


def test_get_ip_address():
    ip = util.get_ip_address()
    assert isinstance(ip, str) and ip.count(".") == 3


def test_find_in_path(tmp_path):
    f = tmp_path / "tool.sh"
    f.write_text("#!/bin/sh\n")
    path = os.pathsep.join(["/nonexistent", str(tmp_path)])
    assert util.find_in_path(path, "tool.sh") == str(f)
    assert util.find_in_path(path, "missing.sh") is None


def test_executor_id_guard(tmp_path):
    d = str(tmp_path)
    assert util.read_executor_id(d) is None
    util.write_executor_id(3, d)
    assert util.read_executor_id(d) == 3


def test_find_free_port():
    host, port = util.find_free_port()
    assert 1024 < port < 65536


def test_chip_claim_partition(tmp_path, monkeypatch):
    monkeypatch.setenv("TFOS_NUM_CHIPS", "4")
    monkeypatch.setenv("TFOS_SCRATCH_ROOT", str(tmp_path))
    a = chip_info.claim_chips(2, "app1", "exec_0")
    b = chip_info.claim_chips(2, "app1", "exec_1")
    assert sorted(a + b) == [0, 1, 2, 3]
    with pytest.raises(RuntimeError):
        chip_info.claim_chips(1, "app1", "exec_2")
    chip_info.release_chips(a, "app1")
    c = chip_info.claim_chips(2, "app1", "exec_2")
    assert sorted(c) == sorted(a)


def test_chip_claim_too_many(monkeypatch, tmp_path):
    monkeypatch.setenv("TFOS_NUM_CHIPS", "2")
    monkeypatch.setenv("TFOS_SCRATCH_ROOT", str(tmp_path))
    with pytest.raises(RuntimeError):
        chip_info.claim_chips(3, "app2", "exec_0")


def test_chipless_host_claims_nothing(monkeypatch, tmp_path):
    monkeypatch.setenv("TFOS_NUM_CHIPS", "0")
    monkeypatch.setenv("TFOS_SCRATCH_ROOT", str(tmp_path))
    assert chip_info.claim_chips(1, "app3", "exec_0") == []


def test_deploy_table_lists_every_tfos_name():
    """DEPLOY.md's environment table and the package's Python name the
    same ``TFOS_*`` variables: a new name is a reviewed line of the table,
    and a name that left the code leaves the table."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    read = set()
    for root, _dirs, files in os.walk(
            os.path.join(repo, "tensorflowonspark_tpu")):
        for fn in files:
            if fn.endswith(".py"):
                with open(os.path.join(root, fn), encoding="utf-8") as f:
                    read |= set(re.findall(r"TFOS_[A-Z0-9_]+", f.read()))
    with open(os.path.join(repo, "DEPLOY.md"), encoding="utf-8") as f:
        rows = re.findall(r"^\| `(TFOS_[A-Z0-9_]+)` \|.*\| (.+?) \|$",
                          f.read(), flags=re.M)
    table = dict(rows)
    assert len(rows) == len(table), "a name is listed twice"
    assert table.keys() == read, (
        sorted(read - table.keys()), sorted(table.keys() - read))
    assert set(table.values()) <= {
        "deployment setting", "limit", "switch", "fault hook"}

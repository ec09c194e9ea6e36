"""chip_smoke.py rehearsed on the CPU, so the script cannot rot between
chip runs: its data, query and answer steps at SF0.01 on one device,
the ``--chips 4`` steps on four of the eight virtual devices, and the
device check itself (``main()`` must refuse a machine with no chip)."""

import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """TPC-H SF0.01 as Parquet, registered over the wire."""
    out = tmp_path_factory.mktemp("chip_smoke")
    paths, frames = chip_smoke.data_step(0.01, 0, str(out))
    server = chip_smoke.serve()
    yield server, paths, frames
    server.stop(grace=0.5)


def test_main_refuses_a_machine_without_a_chip(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    out = capsys.readouterr().out
    assert '"ok"' not in out and '"step": "data"' not in out


def test_main_refuses_a_lower_scale():
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main(["--sf", "0.1"])
    assert exc.value.code not in (0, None)


def test_one_chip_steps_on_one_cpu_device(served, capsys):
    server, paths, frames = served
    records = chip_smoke.run_queries(
        server, paths, chip_smoke.ONE_CHIP_QUERIES, frames,
        conf=lambda q: {"spark.sail.execution.mesh": "off"})
    assert [r["q"] for r in records] == list(chip_smoke.ONE_CHIP_QUERIES)
    for r in records:
        assert r["equals_oracle"] and r["rows"] > 0
        assert r["worst_rel_err"] <= chip_smoke.RTOL
        assert r["first_calls_concurrent"] is True
        assert r["first"]["routes"], "no backend_route was recorded"
        assert r["second_from_result_cache"] in (True, False)
    # on the CPU the native host kernel is active, so the "the chip did
    # it" step must refuse this run
    with pytest.raises(AssertionError):
        chip_smoke.chip_did_it_step(records, "cpu")
    shards = chip_smoke.lineitem_fragment_devices("cpu")
    assert shards["lineitem_fragments"] >= 1
    assert shards["lineitem_device_bytes"] > 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == len(records)


def test_a_wrong_answer_fails_the_answer_step(served):
    server, paths, frames = served
    client, _session = chip_smoke.connect(server, paths)
    got, _seconds = chip_smoke._timed_sql(client, 6)
    expected = chip_smoke.oracle_answer(6, frames)
    assert chip_smoke.answer_step(6, got, expected) <= chip_smoke.RTOL
    import pyarrow as pa
    import pyarrow.compute as pc
    skewed = pa.table(
        [pc.multiply(got.column(0).cast(pa.float64()), 1.00001)],
        names=got.column_names)
    with pytest.raises(AssertionError):
        chip_smoke.answer_step(6, skewed, expected)


def test_four_chip_steps_on_four_virtual_devices(served, monkeypatch):
    server, paths, frames = served
    from sail_tpu.exec import result_cache
    from sail_tpu.parallel import mesh as mesh_mod
    # the one-device test may have left these answers in the result
    # cache, which would serve them before the mesh is asked
    result_cache.RESULT_CACHE.clear()
    four = jax.devices()[:4]
    real_make_mesh = mesh_mod.make_mesh
    # MeshExecutor builds its mesh from every device JAX lists; hold it
    # to four of the eight virtual ones, as a four-chip host would
    monkeypatch.setattr(
        "sail_tpu.parallel.mesh_exec.make_mesh",
        lambda n_devices=None, devices=None:
            real_make_mesh(n_devices, devices or four))
    records = chip_smoke.mesh_run(server, paths, frames, 4)
    assert [r["q"] for r in records] == list(chip_smoke.MESH_QUERIES)
    for r in records:
        assert r["equals_oracle"]
        assert len(r["leaf_bytes_per_device"]) == 4
        assert min(r["leaf_bytes_per_device"].values()) > 0
        assert r["mesh_exchanges"] >= 1
        assert r["mesh_retries"] >= 0

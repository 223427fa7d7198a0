"""3-tier config system (SURVEY A6): TOML node config, ALTER SYSTEM
parameters, SET/SHOW session variables. Reference:
src/common/src/config.rs:137, system_param/mod.rs:97, session_config/."""
import pytest

from risingwave_tpu.config import NodeConfig, SystemParams
from risingwave_tpu.sql import Database


def test_node_config_from_toml(tmp_path):
    p = tmp_path / "rw.toml"
    p.write_text("""
[streaming]
chunk_size = 512
checkpoint_frequency = 3

[storage]
block_cache_blocks = 128
""")
    cfg = NodeConfig.from_toml(str(p))
    assert cfg.streaming.chunk_size == 512
    assert cfg.streaming.checkpoint_frequency == 3
    assert cfg.storage.block_cache_blocks == 128
    assert cfg.streaming.barrier_interval_ms == 1000   # default kept


def test_node_config_rejects_unknown_keys(tmp_path):
    p = tmp_path / "rw.toml"
    p.write_text("[streaming]\nchunk_sz = 1\n")
    with pytest.raises(ValueError, match="unknown config key"):
        NodeConfig.from_toml(str(p))
    p.write_text("[nonsense]\nx = 1\n")
    with pytest.raises(ValueError, match="unknown config sections"):
        NodeConfig.from_toml(str(p))


def test_database_accepts_config_file(tmp_path):
    p = tmp_path / "rw.toml"
    p.write_text("[streaming]\ncheckpoint_frequency = 4\n")
    db = Database(config=str(p))
    assert db.injector.checkpoint_frequency == 4
    assert db.system_params.get("checkpoint_frequency") == 4


def test_session_vars_set_show():
    db = Database()
    assert db.run("SHOW timezone") == ["UTC"]
    db.run("SET timezone TO 'America/New_York'")
    assert db.run("SHOW timezone") == ["America/New_York"]
    db.run("SET extra_float_digits = 3")
    assert db.run("SHOW extra_float_digits") == [3]
    allv = db.run("SHOW ALL")[0]
    assert ("timezone", "America/New_York") in allv
    with pytest.raises(ValueError, match="unrecognized"):
        db.run("SET no_such_var = 1")


def test_alter_system_applies_and_persists(tmp_path):
    d = str(tmp_path)
    db = Database(data_dir=d)
    db.run("ALTER SYSTEM SET checkpoint_frequency = 5")
    assert db.injector.checkpoint_frequency == 5
    assert db.run("SHOW checkpoint_frequency") == [5]
    params = dict(db.run("SHOW PARAMETERS")[0])
    assert params["checkpoint_frequency"] == 5

    db2 = Database(data_dir=d)              # replayed from the DDL log
    assert db2.injector.checkpoint_frequency == 5
    with pytest.raises(ValueError, match="unknown system parameter"):
        db2.run("ALTER SYSTEM SET no_such = 1")


def test_system_params_coercion():
    sp = SystemParams()
    assert sp.set("pause_on_next_bootstrap", "true") is True
    assert sp.set("checkpoint_frequency", "7") == 7
    with pytest.raises(ValueError):
        sp.get("bogus")
    with pytest.raises(ValueError, match=">= 1"):
        sp.set("checkpoint_frequency", 0)


def test_set_accepts_exponent_literal():
    db = Database()
    db.run("SET extra_float_digits = 1e1")
    assert db.run("SHOW extra_float_digits") == [10]


def test_ctor_overrides_config_file(tmp_path):
    p = tmp_path / "rw.toml"
    p.write_text("[streaming]\ncheckpoint_frequency = 4\n")
    db = Database(config=str(p), checkpoint_frequency=1)
    assert db.injector.checkpoint_frequency == 1


def test_device_section_typo_fails_even_when_off(tmp_path):
    p = tmp_path / "rw.toml"
    p.write_text("[device]\nmode = 'off'\ncapcity = 9\n")
    from risingwave_tpu.config import NodeConfig
    with pytest.raises(ValueError, match="unknown config key"):
        NodeConfig.from_toml(str(p))


def test_device_mode_int_is_mesh_shards(tmp_path):
    """One spelling of "n chips": an int device argument — and TOML
    `mode = "<n>"` — is the fused job's shard count."""
    from risingwave_tpu.config import DeviceConfig, resolve_device
    p = tmp_path / "rw.toml"
    p.write_text("[device]\nmode = '8'\n")
    cfg = NodeConfig.from_toml(str(p)).device
    assert cfg.mesh_shards == 8
    assert cfg == resolve_device(8) == DeviceConfig(mesh_shards=8)


def test_device_int_marker_is_fshard_and_reopens(tmp_path):
    import json
    d = str(tmp_path)
    db = Database(data_dir=d, device=8)
    db.run("CREATE TABLE t (k INT, v BIGINT)")
    db.run("CREATE MATERIALIZED VIEW mv AS SELECT k, sum(v) AS s "
           "FROM t GROUP BY k")
    db.run("INSERT INTO t VALUES (1, 10), (2, 20), (1, 5)")
    before = sorted(db.query("SELECT * FROM mv"))
    with open(tmp_path / "device_mode.json") as f:
        assert json.load(f)["mode"] == "single:fshard8:minmax"
    assert Database._device_from_marker(d).mesh_shards == 8
    db2 = Database(data_dir=d, device=8)
    assert sorted(db2.query("SELECT * FROM mv")) == before == [(1, 15),
                                                               (2, 20)]


def test_per_operator_mesh_marker_is_refused(tmp_path):
    """A directory whose state was laid out by the per-operator mesh
    executors is not guessed at: the error names the marker and the
    option that shards a job now."""
    (tmp_path / "device_mode.json").write_text('{"mode": "mesh:8:minmax"}')
    with pytest.raises(ValueError, match=r"mesh:8:minmax.*mesh_shards"):
        Database(data_dir=str(tmp_path), device="auto")
    with pytest.raises(ValueError, match=r"mesh:8:minmax"):
        Database(data_dir=str(tmp_path), device=8)

"""What the engine refuses, in one place: every test of construction here
is driven FROM `engine.CACHE_KIND_REFUSALS`, the table `_refuse_plane`,
`_refuse_config` and `_mixed_unsupported_reason` read, on a tiny
configuration of each kind of cache (DeepSeek's and Xing's latent pool,
MiMo's window pools, granite's state pools) and of the one kind of STEP
that is not a token a sequence (SDAR's block step). The planes asked of a
running engine (disaggregation, prefix export / ingest) stay with each
family's own tests; what PR 46 took out is proved gone at the end."""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import _REFUSABLE_OPTIONS, CACHE_KIND_REFUSALS
from dynamo_tpu.models.config import PRESETS
from dynamo_tpu.parallel.mesh import AXES, MeshConfig
from dynamo_tpu.run import build_parser

from .test_engine import make_engine

# tiny configuration -> (its row of the table, what a mesh is refused with:
# Xing's four streams refuse a mesh before its latent pool is looked at)
TINY = {
    "tiny-mla": ("latent", "latent"),
    "tiny-mimo": ("hybrid", "window beside full attention"),
    "tiny-granite": ("recurrent", "Mamba-2 layers beside attention"),
    "tiny-xing": ("latent", "residual of 4 streams"),
    # not a kind of cache but of step: a block a sequence a pass
    "tiny-sdar": ("dlm", "generation by diffusion over blocks"),
}
# how a configuration asks for each option the table may refuse
ASK = {
    "kv_quantization": dict(kv_quantization="int8"),
    "quantization": dict(quantization="int8"),
    "host_kv_pages": dict(host_kv_pages=8),
    "spec_decode": dict(spec_decode=True),
    "mixed_batching": dict(mixed_batching=True),
}


def _cfg(preset):
    cfg = PRESETS[preset].with_(dtype="float32")
    if preset == "tiny-mimo":  # heads every axis of size 2 divides
        cfg = cfg.with_(num_kv_heads=2, swa_num_kv_heads=2)
    return cfg


def _sentence(kind):
    """The words of the kind's `why` that say which cache it is."""
    return CACHE_KIND_REFUSALS[kind]["why"].split(
        "served with ")[1].split(" ('")[0]


def test_the_table_names_only_options_a_configuration_can_ask():
    for kind, row in CACHE_KIND_REFUSALS.items():
        assert hasattr(PRESETS["tiny"], kind)
        assert set(row) == {"why", "options", "mixed"}
        assert set(row["options"]) <= set(_REFUSABLE_OPTIONS)
    assert set(_REFUSABLE_OPTIONS) == set(ASK) | {"mesh"}
    assert {kind for kind, _ in TINY.values()} == set(CACHE_KIND_REFUSALS)


@pytest.mark.parametrize("preset,option", [
    (preset, option) for preset, (kind, _) in TINY.items()
    for option in ASK
])
def test_cache_kind_refuses_at_construction(preset, option):
    """Every option a kind's row lists is refused with the kind's
    sentence, the option by name and the row's words of why; the one
    option no row of a latent cache lists, mixed_batching, is refused by
    the row's `mixed` sentence."""
    kind, _ = TINY[preset]
    row = CACHE_KIND_REFUSALS[kind]
    with pytest.raises(ValueError, match=_sentence(kind)) as err:
        make_engine(model=_cfg(preset), **ASK[option])
    said = str(err.value)
    if option in row["options"]:
        named = _REFUSABLE_OPTIONS[option].format(*ASK[option].values())
        assert said.startswith(named) and row["options"][option] in said
    else:
        assert option == "mixed_batching" and said == row["mixed"]


@pytest.mark.parametrize("preset,axis", [
    (preset, axis) for preset in TINY for axis in AXES])
def test_cache_kind_refuses_every_mesh_axis(preset, axis):
    """tp, the ring (sp) executor, ep, dp: no kind of cache but K and V
    pools under one list of page ids has a rule for any of them."""
    kind, sentence = TINY[preset]
    assert "mesh" in CACHE_KIND_REFUSALS[kind]["options"]
    with pytest.raises(ValueError, match=sentence):
        make_engine(model=_cfg(preset), mesh=MeshConfig(**{axis: 2}),
                    prefill_chunk=128)


@pytest.mark.parametrize("preset", sorted(TINY))
def test_a_running_engine_refuses_from_the_same_table(preset):
    """The page inject / extract programs, the device-path transfer and
    the runtime toggle of mixed batching read the row construction read."""
    from dynamo_tpu.engine.kv_transfer import device_transfer_kv

    kind, _ = TINY[preset]
    engine = make_engine(model=_cfg(preset))
    assert engine._cache_kind() == kind
    assert type(engine.kv).__name__ == "KVCache"
    slots = jnp.zeros((1,), jnp.int32)
    for refused in (
        lambda: engine._extract_fn(engine.kv, slots),
        lambda: engine._inject_fn(engine.kv, slots, None, None),
        lambda: device_transfer_kv(engine, engine, [1], [2], 8),
        lambda: engine._refuse_plane("anything that moves pages"),
    ):
        with pytest.raises(ValueError, match=_sentence(kind)):
            refused()
    engine.config.mixed_batching = True
    assert engine._mixed_unsupported_reason() == (
        CACHE_KIND_REFUSALS[kind]["mixed"])


def test_a_plain_cache_refuses_nothing():
    engine = make_engine()
    assert engine._cache_kind() is None
    engine._refuse_plane("anything that moves pages")
    assert engine._mixed_unsupported_reason() is None


# ------------------------------------------------- what PR 46 took out is gone

@pytest.mark.parametrize("field", [
    "mixed_spec", "mixed_decode_priority", "priority_scheduling",
    "decode_ready_frac", "prefill_batch_window_s", "prefill_batch_min_rows"])
def test_engine_config_lost_the_field(field):
    assert len(dataclasses.fields(EngineConfig)) == 34
    with pytest.raises(TypeError, match=field):
        EngineConfig(**{field: 1})


def test_the_mesh_has_no_pipeline_axis():
    assert AXES == ("dp", "ep", "sp", "tp")
    with pytest.raises(TypeError, match="pp"):
        MeshConfig(pp=2)


def test_the_cli_has_no_pipeline_flag(capsys):
    with pytest.raises(SystemExit):
        build_parser().parse_args(["in=http", "out=jax", "--pp", "2"])
    assert "--pp" in capsys.readouterr().err

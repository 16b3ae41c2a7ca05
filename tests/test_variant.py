"""The one dispatch decision (utils/pallas_util.resolve_variant).

Which kernel set a prove runs is resolved in one place from what the code
observes (backend, the mesh and its mode, the active field, `force_xla`)
and two overrides (BOOJUM_TPU_LIMB_RESIDENT, BOOJUM_TPU_MESH_MODE). Each
case below is one position of that decision; the record's dict is the flag
half of the AOT bundle key and `enumerate_kernels` names only the resolved
variant's kernels.
"""

import contextlib

import jax
import pytest

from boojum_tpu.utils.pallas_util import (
    force_xla,
    pallas_enabled,
    resolve_variant,
)
from proving import fma_assembly, mesh_2x4, small_config

ON_TPU = jax.default_backend() == "tpu"
NATIVE = "planes" if ON_TPU else "u64"


def _enumerated(kw=None):
    from boojum_tpu.prover.precompile import enumerate_kernels

    return [
        s.name
        for s in enumerate_kernels(fma_assembly(), small_config(), **(kw or {}))
    ]


def _check_names(names, variant):
    """Only the resolved variant's kernels: `*_limbres` names on planes,
    none of them on u64, `_bb` names for BabyBear alone."""
    assert names
    if variant.field == "babybear":
        assert all("_bb" in n for n in names), names
        return
    assert not any("_bb" in n for n in names), names
    sm = "_sm" if variant.mesh == "shard_map" else ""
    tagged = [n for n in names if "limbres" in n]
    if variant.planes:
        assert "coset_sweep_terms_limbres" + sm in names
        assert "coset_sweep_terms" + sm not in names
        assert not any(n.startswith("fri_fold_k") for n in names)
    else:
        assert tagged == [], tagged
        assert "coset_sweep_terms" + sm in names


# (id, environment, context, mesh, expected fields or the raised variable)
CASES = [
    ("cpu-unset", {}, None, None,
     dict(representation=NATIVE, mesh=None, field="goldilocks")),
    ("override-on", {"BOOJUM_TPU_LIMB_RESIDENT": "1"}, None, None,
     dict(representation="planes", mesh=None)),
    ("override-on-spelled", {"BOOJUM_TPU_LIMB_RESIDENT": "yes"}, None, None,
     dict(representation="planes")),
    ("override-off", {"BOOJUM_TPU_LIMB_RESIDENT": "0"}, None, None,
     dict(representation="u64")),
    ("override-junk", {"BOOJUM_TPU_LIMB_RESIDENT": "maybe"}, None, None,
     "BOOJUM_TPU_LIMB_RESIDENT"),
    ("force-xla", {"BOOJUM_TPU_LIMB_RESIDENT": "1"}, "force_xla", None,
     dict(representation="u64", pallas=False)),
    ("gspmd-active", {"BOOJUM_TPU_MESH_MODE": "gspmd",
                      "BOOJUM_TPU_LIMB_RESIDENT": "1"}, "active", "mesh",
     dict(representation="u64", pallas=False, mesh="gspmd", fused=False)),
    ("shard-map-argument", {"BOOJUM_TPU_LIMB_RESIDENT": "1"}, None, "mesh",
     dict(representation="planes", mesh="shard_map", fused=True)),
    ("shard-map-active-default", {"BOOJUM_TPU_MESH_MODE": "sm"}, "active",
     "mesh", dict(representation=NATIVE, mesh="shard_map", fused=True)),
    ("mesh-mode-junk", {"BOOJUM_TPU_MESH_MODE": "fast"}, None, "mesh",
     "BOOJUM_TPU_MESH_MODE"),
    ("mesh-mode-unread-without-mesh", {"BOOJUM_TPU_MESH_MODE": "fast"},
     None, None, dict(mesh=None)),
    ("babybear", {"BOOJUM_TPU_FIELD": "babybear",
                  "BOOJUM_TPU_LIMB_RESIDENT": "1"}, None, None,
     dict(representation="u64", field="babybear")),
]


@pytest.mark.parametrize(
    "env,context,mesh,want", [c[1:] for c in CASES], ids=[c[0] for c in CASES]
)
def test_resolve_variant(monkeypatch, env, context, mesh, want):
    from boojum_tpu.parallel.sharding import prover_mesh, shard_map_mesh
    from boojum_tpu.prover.aot import variant_fingerprint

    if mesh is not None and len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    for name in ("BOOJUM_TPU_LIMB_RESIDENT", "BOOJUM_TPU_MESH_MODE",
                 "BOOJUM_TPU_FIELD"):
        monkeypatch.delenv(name, raising=False)
    for name, value in env.items():
        monkeypatch.setenv(name, value)
    m = mesh_2x4() if mesh is not None else None
    with contextlib.ExitStack() as stack:
        if context == "force_xla":
            stack.enter_context(force_xla())
        if context == "active":
            stack.enter_context(prover_mesh(m))
            args = ()  # the resolver finds the active mesh itself
        else:
            args = (m,)  # asked about a mesh that is NOT active
        if isinstance(want, str):
            with pytest.raises(ValueError, match=want):
                resolve_variant(*args)
            return
        v = resolve_variant(*args)
        for key, value in want.items():
            assert getattr(v, key) == value, (key, v)
        # what follows from the record
        assert v.pallas == (
            ON_TPU and context != "force_xla" and v.mesh != "gspmd"
        )
        assert v.planes == (v.representation == "planes")
        if not v.pallas:
            assert pallas_enabled() is False
        if context == "active":
            assert shard_map_mesh() is (m if v.mesh == "shard_map" else None)
            assert shard_map_mesh(v) is shard_map_mesh()
        else:
            assert shard_map_mesh() is None  # nothing is active
        # the record's dict is the flag half of the bundle key
        fp = variant_fingerprint(m) if m is not None and context != "active" \
            else variant_fingerprint()
        assert {k: fp[k] for k in v.as_dict()} == v.as_dict()
        assert set(fp) == set(v.as_dict()) | {"mesh_shape", "stream_lde_bytes"}
        # and the library names that variant's kernels alone (a GSPMD
        # prove dispatches its own sequenced graphs: no library)
        if v.mesh != "gspmd":
            names = _enumerated({"mesh_shape": m} if m is not None else None)
            _check_names(names, v)
            assert any(n.endswith("_sm") for n in names) == (m is not None)

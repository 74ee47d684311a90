"""`io/yaml_loader.py`: manifests decode through libyaml's C parser where
PyYAML has it, to the same objects as PyYAML's pure-Python parser."""

import glob
import json
import os

import pytest
import yaml

from simtpu.io import yaml_loader
from simtpu.io.yaml_loader import (
    SourcedText,
    decode_yaml_content,
    get_objects_from_yaml_content,
    load_resources,
)
from simtpu.obs.metrics import REGISTRY
from simtpu.synth import synth_apps, synth_cluster
from simtpu.workloads.expand import SOURCE_KEY, make_valid_pods_by_deployment

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = os.path.join(REPO, "examples")
EXAMPLE_FILES = sorted(glob.glob(os.path.join(EXAMPLES, "**", "*.y*ml"), recursive=True))

needs_libyaml = pytest.mark.skipif(
    not hasattr(yaml, "CSafeLoader"), reason="PyYAML built without libyaml")

LOADERS = {"libyaml": getattr(yaml, "CSafeLoader", None), "pure": yaml.SafeLoader}


def _generated() -> list:
    """Nodes, deployments and their pods as the synthetic generators make
    them: taints, GPU and storage annotations, affinity, spread."""
    nodes = synth_cluster(6, seed=3, gpu_frac=0.3, storage_frac=0.5).nodes
    deps = [r for app in synth_apps(120, seed=5, pods_per_deployment=20, spread_frac=0.3,
                                    gpu_frac=0.2, storage_frac=0.2)
            for r in app.resource.deployments]
    pods = [p for d in deps[:2] for p in make_valid_pods_by_deployment(d)]
    return nodes + deps + pods


EDGE_SCALARS = """\
kind: Edge
bools: [yes, No, on, OFF, y, n, true]
octal: 012
exp_float: 1e3
float: 1.0e+3
quoted: "012"
date: 2002-12-14
timestamp: 2001-12-14t21:59:43.10-05:00
tilde: ~
empty:
inf: .inf
ninf: -.Inf
hex: 0x1F
sexagesimal: 190:20:30
anchors:
  base: &base {cpu: "1", memory: 1Gi}
  merged:
    <<: *base
    memory: 2Gi
  alias: *base
"""

MULTI_DOC = """\
---
...
---
kind: Pod
metadata: {name: a}
...
---
# a comment alone
---
kind: Node
metadata: {name: n}
---
- a list
---
a plain scalar
---
apiVersion: v1
...
"""


def _read(path: str) -> str:
    with open(path) as f:
        return f.read()


def _texts():
    gen = _generated()
    cases = [pytest.param(_read(p), id=os.path.relpath(p, EXAMPLES)) for p in EXAMPLE_FILES]
    cases += [
        pytest.param(yaml.safe_dump_all(gen, default_flow_style=False), id="block-dump"),
        pytest.param("\n---\n".join(json.dumps(d, separators=(",", ":")) for d in gen),
                     id="compact-json"),
        pytest.param(EDGE_SCALARS, id="edge-scalars"),
        pytest.param(MULTI_DOC, id="multi-doc"),
    ]
    return cases


def _outcome(monkeypatch, loader, text):
    """What `decode_yaml_content` gives under one loader: the objects, or
    the class of the error it raised (chart templates are not YAML)."""
    monkeypatch.setattr(yaml_loader, "_LOADER", loader)
    try:
        return decode_yaml_content(text)
    except yaml.YAMLError as exc:
        return type(exc)


@needs_libyaml
def test_libyaml_is_the_default_loader():
    assert yaml_loader._LOADER is yaml.CSafeLoader


@needs_libyaml
@pytest.mark.parametrize("text", _texts())
def test_libyaml_decodes_as_pure_python(monkeypatch, text):
    want = _outcome(monkeypatch, yaml.SafeLoader, text)
    got = _outcome(monkeypatch, yaml.CSafeLoader, text)
    assert got == want
    assert _outcome(monkeypatch, yaml.CSafeLoader, text.encode()) == want


@needs_libyaml
def test_edge_scalars_and_empty_documents():
    (edge,) = decode_yaml_content(EDGE_SCALARS)
    assert edge["bools"] == [True, False, True, False, "y", "n", True]
    assert (edge["octal"], edge["exp_float"], edge["float"], edge["quoted"]) == (
        10, "1e3", 1000.0, "012")
    assert edge["tilde"] is None and edge["empty"] is None
    assert (edge["hex"], edge["sexagesimal"]) == (31, 685230)
    assert edge["anchors"]["merged"] == {"cpu": "1", "memory": "2Gi"}
    assert [o["kind"] for o in decode_yaml_content(MULTI_DOC)] == ["Pod", "Node"]


@needs_libyaml
def test_sourced_text_decodes_and_stamps_source():
    path = os.path.join(EXAMPLES, "cluster", "demo", "nodes.yaml")
    with open(path) as f:
        text = SourcedText(f.read(), path)
    objs = decode_yaml_content(text)
    assert objs and objs == decode_yaml_content(str(text))
    res = get_objects_from_yaml_content([text])
    assert len(res.nodes) == len(objs)
    assert all(n[SOURCE_KEY] == path for n in res.nodes)


@pytest.mark.parametrize("name", [pytest.param("libyaml", marks=needs_libyaml), "pure"])
def test_load_resources_same_under_each_loader(monkeypatch, name):
    """`load_resources` gives the same objects with libyaml patched away,
    and `ingest.libyaml_docs` counts exactly the documents libyaml decoded."""
    demo = os.path.join(EXAMPLES, "cluster", "demo")
    want = load_resources(demo)
    monkeypatch.setattr(yaml_loader, "_LOADER", LOADERS[name])
    before = REGISTRY.snapshot()
    got = load_resources(demo)
    delta = REGISTRY.delta_since(before)
    assert got == want
    assert delta["ingest.docs"] == len(got.nodes) + len(got.daemon_sets) + len(got.deployments)
    expect = delta["ingest.docs"] if name == "libyaml" else 0
    assert delta.get("ingest.libyaml_docs", 0) == expect


@pytest.mark.parametrize("name", [pytest.param("libyaml", marks=needs_libyaml), "pure"])
@pytest.mark.parametrize("bad", ["kind: Pod\nspec: [1, 2\n", "kind: Pod\n\tspec: {}\n",
                                 "kind: Pod\nmetadata: name: a\n"])
def test_malformed_manifest_raises_yaml_error(monkeypatch, tmp_path, name, bad):
    (tmp_path / "bad.yaml").write_text(bad)
    monkeypatch.setattr(yaml_loader, "_LOADER", LOADERS[name])
    with pytest.raises(yaml.YAMLError):
        load_resources(str(tmp_path))

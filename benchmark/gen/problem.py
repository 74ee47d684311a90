"""One generated problem: the manifests `simtpu apply` reads, and beside
them the same numbers in plain form for the reference check."""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import List, Optional

from .synth import GroupSpec, NodeSpec


def group_of(pod: dict) -> str:
    """The group a pod object belongs to, by what the generator put in
    it: a Deployment's pods carry its `app` label; a bare pod is named
    `<template>-<index>`."""
    meta = pod.get("metadata") or {}
    app = (meta.get("labels") or {}).get("app")
    if app:
        return app
    return meta.get("name", "").rsplit("-", 1)[0]


def _dump(path: str, docs: List[dict]) -> None:
    # JSON is YAML: one document per object, the loader's multi-doc split
    with open(path, "w") as f:
        f.write("\n---\n".join(json.dumps(d, separators=(",", ":")) for d in docs))


@dataclass
class Problem:
    nodes: List[dict]
    node_specs: List[NodeSpec]
    workloads: List[dict]
    groups: List[GroupSpec]
    template: Optional[dict] = None
    template_spec: Optional[NodeSpec] = None
    bound_pods: List[dict] = field(default_factory=list)
    storage: bool = False  # apply with `-e open-local`

    def write(self, root: str, workloads: Optional[List[dict]] = None) -> str:
        """Write a simon config and its directories under `root`; returns
        the config's path."""
        cluster = os.path.join(root, "cluster")
        app = os.path.join(root, "app")
        os.makedirs(cluster, exist_ok=True)
        os.makedirs(app, exist_ok=True)
        _dump(os.path.join(cluster, "nodes.yaml"), self.nodes)
        if self.bound_pods:
            _dump(os.path.join(cluster, "pods.yaml"), self.bound_pods)
        _dump(os.path.join(app, "workloads.yaml"),
              self.workloads if workloads is None else workloads)
        lines = [
            "apiVersion: simon/v1alpha1", "kind: Config",
            "metadata: {name: bench}", "spec:",
            f"  cluster: {{customConfig: {json.dumps(cluster)}}}",
            "  appList:", f"    - {{name: bench, path: {json.dumps(app)}}}",
        ]
        if self.template is not None:
            tmpl = os.path.join(root, "newnode.yaml")
            _dump(tmpl, [self.template])
            lines.append(f"  newNode: {json.dumps(tmpl)}")
        path = os.path.join(root, "simon-config.yaml")
        with open(path, "w") as f:
            f.write("\n".join(lines) + "\n")
        return path

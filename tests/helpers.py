"""Builders for test fixtures, mirroring the shapes the reference's
table-driven tests construct in memory (predicates_test.go, priorities_test.go)."""

from __future__ import annotations

import contextlib
import json
import os
from typing import Iterator, Optional

from kubernetes_tpu.api import types as api


@contextlib.contextmanager
def compile_cache_at(path: str) -> Iterator[None]:
    """Run a block with the persistent compile cache at ``path``, placed
    the way a deployment places it — ``JAX_COMPILATION_CACHE_DIR``.  JAX
    reads that variable only at import, so mid-process the helper also
    applies it to ``jax.config`` and drops JAX's already-opened cache
    object; ``engine.compile_cache`` itself must set no directory.  The
    previous placement is restored on exit."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as jcc

    from kubernetes_tpu.engine import compile_cache
    prev_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    prev_dir = jax.config.jax_compilation_cache_dir

    def place(env: Optional[str], directory: Optional[str]) -> None:
        if env is None:
            os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
        else:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = env
        jax.config.update("jax_compilation_cache_dir", directory)
        jcc.reset_cache()
        compile_cache._reset_for_tests()

    place(path, path)
    try:
        yield
    finally:
        place(prev_env, prev_dir)
        compile_cache.configure()


def make_node(name: str, milli_cpu: int = 4000, memory: int = 16 * 1024**3,
              pods: int = 110, gpu: int = 0, labels: Optional[dict] = None,
              taints: Optional[list[dict]] = None,
              conditions: Optional[list[tuple[str, str]]] = None,
              images: Optional[list[tuple[list[str], int]]] = None,
              unschedulable: bool = False,
              annotations: Optional[dict] = None) -> api.Node:
    ann = dict(annotations or {})
    if taints is not None:
        ann[api.TAINTS_ANNOTATION_KEY] = json.dumps(taints)
    conds = [api.NodeCondition(type=t, status=s)
             for t, s in (conditions or [("Ready", "True")])]
    return api.Node(
        name=name, labels=dict(labels or {}), annotations=ann,
        unschedulable=unschedulable,
        allocatable_milli_cpu=milli_cpu, allocatable_memory=memory,
        allocatable_gpu=gpu, allocatable_pods=pods, conditions=conds,
        images=[api.ContainerImage(names=tuple(ns), size_bytes=sz)
                for ns, sz in (images or [])])


_POD_SEQ = [0]


def make_pod(name: str = "", namespace: str = "default",
             cpu: Optional[str | int] = None, memory: Optional[str | int] = None,
             gpu: Optional[int] = None, labels: Optional[dict] = None,
             node_selector: Optional[dict] = None, node_name: str = "",
             host_ports: Optional[list[int]] = None,
             affinity: Optional[dict] = None,
             tolerations: Optional[list[dict]] = None,
             volumes: Optional[list[api.Volume]] = None,
             images: Optional[list[str]] = None,
             n_containers: int = 1,
             deleted: bool = False) -> api.Pod:
    if not name:
        _POD_SEQ[0] += 1
        name = f"pod-{_POD_SEQ[0]}"
    requests: dict = {}
    if cpu is not None:
        requests["cpu"] = cpu
    if memory is not None:
        requests["memory"] = memory
    if gpu is not None:
        requests["alpha.kubernetes.io/nvidia-gpu"] = gpu
    containers = []
    img_list = images if images is not None else [""] * n_containers
    for i, img in enumerate(img_list):
        ports = []
        if i == 0 and host_ports:
            ports = [api.ContainerPort(host_port=hp) for hp in host_ports]
        containers.append(api.Container(
            name=f"c{i}", image=img, requests=dict(requests) if i == 0 else {},
            ports=ports))
    if not containers:
        containers = [api.Container(name="c0", requests=requests)]
    ann = {}
    if affinity is not None:
        ann[api.AFFINITY_ANNOTATION_KEY] = json.dumps(affinity)
    if tolerations is not None:
        ann[api.TOLERATIONS_ANNOTATION_KEY] = json.dumps(tolerations)
    return api.Pod(name=name, namespace=namespace, labels=dict(labels or {}),
                   annotations=ann, node_name=node_name,
                   node_selector=dict(node_selector or {}),
                   containers=containers, volumes=list(volumes or []),
                   deletion_timestamp=1.0 if deleted else None)

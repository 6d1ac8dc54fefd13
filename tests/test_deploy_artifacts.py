"""Deployment artifacts stay valid and internally consistent.

The reference ships a DaemonSet + RBAC + kustomize deployment
(/root/reference SURVEY §4: misc/snapshotter/base, tests/e2e/k8s); no
cluster exists here, so these assert the manifests parse, reference each
other by the right names, and point at entry points and files that exist.
"""

from __future__ import annotations

import os

import yaml

MISC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "misc", "snapshotter")
K8S = os.path.join(MISC, "k8s")


def _load_all(name: str) -> list[dict]:
    with open(os.path.join(K8S, name)) as f:
        return [d for d in yaml.safe_load_all(f) if d]


class TestK8sManifests:
    def test_rbac_parses_and_binds_service_account(self):
        docs = _load_all("rbac.yaml")
        kinds = {d["kind"]: d for d in docs}
        assert set(kinds) == {"ServiceAccount", "ClusterRole", "ClusterRoleBinding"}
        sa = kinds["ServiceAccount"]["metadata"]
        binding = kinds["ClusterRoleBinding"]
        assert binding["subjects"][0]["name"] == sa["name"]
        assert binding["subjects"][0]["namespace"] == sa["namespace"]
        assert binding["roleRef"]["name"] == kinds["ClusterRole"]["metadata"]["name"]
        # the kubeconfig keychain needs secret read access
        rules = kinds["ClusterRole"]["rules"]
        assert any("secrets" in r["resources"] for r in rules)

    def test_daemonset_parses_and_references_real_entry(self):
        (ds,) = _load_all("daemonset.yaml")
        assert ds["kind"] == "DaemonSet"
        spec = ds["spec"]["template"]["spec"]
        (ctr,) = spec["containers"]
        # entry module must exist and be runnable
        cmd = ctr["command"]
        assert "nydus_snapshotter_tpu.cmd.snapshotter" in cmd
        import importlib

        assert importlib.util.find_spec("nydus_snapshotter_tpu.cmd.snapshotter")
        # serving plane needs privilege + /dev/fuse
        assert ctr["securityContext"]["privileged"] is True
        mounts = {m["name"] for m in ctr["volumeMounts"]}
        vols = {v["name"] for v in spec["volumes"]}
        assert mounts <= vols
        assert "dev-fuse" in mounts
        # service account matches RBAC
        rbac_docs = _load_all("rbac.yaml")
        sa_name = next(d for d in rbac_docs if d["kind"] == "ServiceAccount")["metadata"]["name"]
        assert spec["serviceAccountName"] == sa_name

    def test_kustomization_references_existing_files(self):
        with open(os.path.join(MISC, "kustomization.yaml")) as f:
            k = yaml.safe_load(f)
        for res in k["resources"]:
            assert os.path.exists(os.path.join(MISC, res)), res
        for gen in k["configMapGenerator"]:
            for entry in gen["files"]:
                rel = entry.split("=", 1)[1] if "=" in entry else entry
                # kustomize's default load restrictor rejects paths above
                # the kustomization root
                assert not rel.startswith(".."), rel
                assert os.path.exists(os.path.join(MISC, rel)), rel
        # the generated ConfigMap name is the one the DaemonSet consumes
        (ds,) = _load_all("daemonset.yaml")
        cm_vols = [
            v["configMap"]["name"]
            for v in ds["spec"]["template"]["spec"]["volumes"]
            if "configMap" in v
        ]
        assert cm_vols == [k["configMapGenerator"][0]["name"]]
        # the nydusd runtime template referenced by config.toml is shipped
        # in the ConfigMap (cmd/snapshotter.py silently skips a missing one)
        shipped = {
            (e.split("=", 1)[0] if "=" in e else os.path.basename(e))
            for g in k["configMapGenerator"]
            for e in g["files"]
        }
        assert "nydusd-config.fusedev.json" in shipped

    def test_grpc_socket_dir_is_host_mounted(self):
        # config.toml's UDS address must live on a hostPath mount or host
        # containerd can never dial the snapshotter
        import tomllib

        with open(os.path.join(MISC, "config.toml"), "rb") as f:
            cfg = tomllib.load(f)
        sock_dir = os.path.dirname(cfg["address"])
        (ds,) = _load_all("daemonset.yaml")
        spec = ds["spec"]["template"]["spec"]
        host_mounts = {
            m["mountPath"]
            for m in spec["containers"][0]["volumeMounts"]
            if any(
                v["name"] == m["name"] and "hostPath" in v for v in spec["volumes"]
            )
        }
        assert sock_dir in host_mounts, (sock_dir, host_mounts)

    def test_config_toml_is_loadable(self):
        from nydus_snapshotter_tpu.config.config import load_config

        cfg = load_config(os.path.join(MISC, "config.toml"))
        assert cfg.version == 1

    def test_dockerfile_builds_native_and_runs_entry(self):
        with open(os.path.join(MISC, "Dockerfile")) as f:
            content = f.read()
        assert "make -C nydus_snapshotter_tpu/native" in content
        assert "nydus_snapshotter_tpu.cmd.snapshotter" in content

"""Declarative Serve deploys: YAML/JSON config -> running deployments.

Reference: ``python/ray/serve/schema.py`` (ServeDeploySchema) + the
``serve build`` / ``serve deploy`` CLI — a config file names applications
by import path with per-deployment overrides, so deploys are repeatable
artifacts instead of scripts.

Config shape::

    applications:
      - name: myapp                  # optional
        import_path: my_module:app   # Application or Deployment object
        args: {}                     # bound at deploy when import is a
                                     # Deployment (ignored for Application)
        deployments:                 # optional per-deployment overrides
          - name: MyDeployment
            num_replicas: 3
            max_ongoing_requests: 8
            ray_actor_options: {num_cpus: 1}
            init_kwargs:             # constructor overrides, merged over
              num_slots: 16          # bind() kwargs (e.g. the continuous
              max_len: 1024          # -batching engine knobs)
              block_size: 64         # paged-KV plane knobs ride the same
              kv_dtype: int8         # path (paged / block_size / kv_dtype
              sampling:              # / num_blocks / sampling)
                temperature: 0.7
                top_p: 0.9
    role_groups:                     # disaggregated prefill/decode: a
      - name: llm                    # LOGICAL name mapping to deployed
        prefill: llm-prefill         # (prefill, decode) deployments —
        decode: llm-decode           # the ingress classifies + splits
"""

from __future__ import annotations

import importlib
import json
import logging
from typing import Any, Dict, List

logger = logging.getLogger(__name__)

_OVERRIDABLE = ("num_replicas", "max_ongoing_requests",
                "autoscaling_config", "placement_strategy",
                "ray_actor_options", "init_kwargs")


def _load_import_path(import_path: str):
    module_name, _, attr = import_path.partition(":")
    if not attr:
        raise ValueError(
            f"import_path {import_path!r} must look like 'module:attribute'")
    module = importlib.import_module(module_name)
    target = module
    for part in attr.split("."):
        target = getattr(target, part)
    return target


def _apply_overrides(deployment, overrides: Dict[str, Any]):
    """Return a COPY of the deployment with overrides applied — mutating
    the imported module-global Deployment would leak this config's values
    into every later deploy in the process."""
    kwargs: Dict[str, Any] = {}
    for key, value in overrides.items():
        if key == "name":
            continue
        if key not in _OVERRIDABLE:
            raise ValueError(f"unknown deployment override {key!r} "
                             f"(supported: {_OVERRIDABLE})")
        if key in ("num_replicas", "max_ongoing_requests"):
            kwargs[key] = int(value)
        elif key in ("autoscaling_config", "ray_actor_options",
                     "init_kwargs"):
            kwargs[key] = dict(value)
        else:
            kwargs[key] = value
    return deployment.options(**kwargs) if kwargs else deployment


def deploy_config_data(text: str) -> List[str]:
    """Deploy from a YAML/JSON document string; returns deployed names."""
    try:
        cfg = json.loads(text)
    except json.JSONDecodeError:
        import yaml

        cfg = yaml.safe_load(text)
    return deploy_config_dict(cfg or {})


def deploy_config_file(path: str) -> List[str]:
    with open(path) as f:
        return deploy_config_data(f.read())


def deploy_config_dict(cfg: Dict[str, Any]) -> List[str]:
    from ray_tpu.serve.api import (Application, Deployment,
                                   register_role_group, run)

    deployed: List[str] = []
    for app_cfg in cfg.get("applications", []):
        target = _load_import_path(app_cfg["import_path"])
        if isinstance(target, Deployment):
            args = app_cfg.get("args", {})
            target = target.bind(**args) if isinstance(args, dict) \
                else target.bind(*args)
        if not isinstance(target, Application):
            raise TypeError(
                f"{app_cfg['import_path']} resolved to {type(target)}; "
                f"expected a Deployment or a bound Application")
        dep = target.deployment
        for ov in app_cfg.get("deployments", []):
            if ov.get("name", dep.name) == dep.name:
                dep = _apply_overrides(dep, ov)
        if dep is not target.deployment:
            target = Application(dep, target.args, target.kwargs)
        run(target, name=app_cfg.get("name", dep.name))
        deployed.append(dep.name)
        logger.info("deployed %s from %s", dep.name,
                    app_cfg["import_path"])
    for group in cfg.get("role_groups", []):
        # Declared AFTER the applications deploy so the pair the group
        # names already exists when the first classified request lands.
        register_role_group(group["name"], prefill=group["prefill"],
                            decode=group["decode"])
        logger.info("registered role group %s -> prefill=%s decode=%s",
                    group["name"], group["prefill"], group["decode"])
    return deployed


def build_config(*apps) -> Dict[str, Any]:
    """Emit a deployable config dict from Application objects
    (reference: ``serve build``). import_path must be filled in by the
    caller for anything not importable by name."""
    out = {"applications": []}
    for app in apps:
        dep = app.deployment
        mod = getattr(dep._cls_or_fn, "__module__", "__main__")
        qual = getattr(dep._cls_or_fn, "__qualname__", dep.name)
        out["applications"].append({
            "name": dep.name,
            "import_path": f"{mod}:{qual}",
            "deployments": [{
                "name": dep.name,
                "num_replicas": dep.num_replicas,
                "max_ongoing_requests": dep.max_ongoing_requests,
            }],
        })
    return out


__all__ = ["deploy_config_file", "deploy_config_data",
           "deploy_config_dict", "build_config"]

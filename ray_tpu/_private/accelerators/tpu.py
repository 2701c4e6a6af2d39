"""TPU accelerator manager: chip detection, visibility isolation, and
pod-slice scheduling resources.

Re-design of the reference's TPU support (reference:
python/ray/_private/accelerators/tpu.py:71 TPUAcceleratorManager — chip
autodetect :48, TPU_VISIBLE_CHIPS isolation :155, pod-type detection :198,
pod-slice resources :334). Differences: slice gang scheduling is meant to
be first-class here — a node in a TPU pod slice advertises
  TPU-{accelerator_type}-head : 1.0   (worker 0 only)
  tpu-slice:{pod_name}        : 1.0   (every worker in the slice)
so a trainer reserves a whole slice by taking the head resource and then
fanning out per-host actors pinned by the pod-name resource.
"""

from __future__ import annotations

import glob
import json
import logging
import os
import re
import sys
from typing import Dict, List, Optional

from ray_tpu._private.accelerators.accelerator import AcceleratorManager

logger = logging.getLogger(__name__)

TPU_VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
# libtpu's own sub-host partition variables: a process that is shown
# fewer chips than the host has must also be told the shape of what it
# sees (reference: tpu.py:155 set_current_process_visible_accelerator_ids)
TPU_CHIPS_PER_HOST_BOUNDS_ENV = "TPU_CHIPS_PER_HOST_BOUNDS"
TPU_HOST_BOUNDS_ENV = "TPU_HOST_BOUNDS"
_SUBHOST_BOUNDS = {1: "1,1,1", 2: "1,2,1"}
JAX_PLATFORMS_ENV = "JAX_PLATFORMS"
# libtpu's switch for hosts that have no metadata server to ask
TPU_SKIP_MDS_QUERY_ENV = "TPU_SKIP_MDS_QUERY"
# GCE TPU-VM metadata (gated: zero-egress or non-GCE boxes skip silently)
GCE_TPU_ACCEL_TYPE_ENV = "TPU_ACCELERATOR_TYPE"   # e.g. v4-32, v5litepod-8
GCE_TPU_NAME_ENV = "TPU_NAME"
GCE_TPU_WORKER_ID_ENV = "TPU_WORKER_ID"

_SINGLE_HOST_CHIPS = {"v2": 4, "v3": 4, "v4": 4, "v5litepod": 8, "v5p": 4,
                      "v6e": 8}


def _chips_per_host(accel_type: str) -> int:
    gen = accel_type.split("-")[0]
    return _SINGLE_HOST_CHIPS.get(gen, 4)


def _chips_on_this_host(accel_type: str) -> int:
    """Chips one host of this accelerator type holds: a sub-host type
    (v5litepod-1, v5litepod-4) has fewer than a full host's."""
    m = re.match(r"^([^-]+)-(\d+)$", accel_type)
    if not m:
        return _chips_per_host(accel_type)
    gen, count = m.group(1), int(m.group(2))
    total = count // 2 if gen in ("v2", "v3", "v4", "v5p") else count
    return max(1, min(total, _chips_per_host(accel_type)))


# ------------------------------------------------- one process per chip
# A chip belongs to one process at a time, and a process keeps the one it
# opened until it exits. So the runtime decides, per worker process, which
# JAX backends it may open: a worker starts pinned to the CPU, and only a
# lease that carries chips lifts the pin (to what the node itself was
# started with) — before JAX opens a backend, which is final.
_NEVER_PINNED = object()
_node_jax_platforms = _NEVER_PINNED    # the node's own setting, once pinned


def _pin_jax_platforms(value: Optional[str]) -> None:
    """Set which JAX backends this process may open: through the env var
    (read when jax is imported) and, where jax is imported already,
    through its config. Raises once a different backend choice is open."""
    global _node_jax_platforms
    if _node_jax_platforms is _NEVER_PINNED:
        _node_jax_platforms = os.environ.get(JAX_PLATFORMS_ENV) or None
    if value:
        os.environ[JAX_PLATFORMS_ENV] = value
    else:
        os.environ.pop(JAX_PLATFORMS_ENV, None)
    jax = sys.modules.get("jax")
    if jax is None or (jax.config.jax_platforms or None) == (value or None):
        return
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized():
        raise RuntimeError(
            f"this process already opened JAX backends "
            f"{sorted(xla_bridge.backends())} and cannot move to "
            f"{value or 'the default platform'}: a chip lease needs a "
            f"worker process of its own")
    jax.config.update("jax_platforms", value or None)


# --------------------------------------------------- GCE metadata autodetect
# Real TPU-VMs publish accelerator-type / worker-number / instance-id on the
# GCE metadata server (reference: tpu.py:198 pod-type detection). Consulted
# BEFORE the env-var fallback so unattended TPU-VMs work with no env setup;
# gated behind a DMI platform sniff + short timeout + negative caching so
# non-GCE boxes (and unit tests) never pay a network wait.
_GCE_METADATA_URL = "http://metadata.google.internal/computeMetadata/v1/"
_GCE_TIMEOUT_S = 0.5
_metadata_cache: Dict[str, Optional[str]] = {}


def _on_gce() -> bool:
    if os.environ.get("RAY_TPU_DISABLE_GCE_METADATA") \
            or os.environ.get(TPU_SKIP_MDS_QUERY_ENV):
        return False
    try:
        with open("/sys/class/dmi/id/product_name") as f:
            return "Google" in f.read()
    except OSError:
        return False


def _gce_metadata(path: str) -> Optional[str]:
    """One metadata attribute, cached (including misses) per process."""
    if path in _metadata_cache:
        return _metadata_cache[path]
    value = None
    if _on_gce():
        try:
            import urllib.request
            req = urllib.request.Request(
                _GCE_METADATA_URL + path,
                headers={"Metadata-Flavor": "Google"})
            with urllib.request.urlopen(req, timeout=_GCE_TIMEOUT_S) as r:
                if r.status == 200:
                    value = r.read().decode().strip() or None
        except Exception:
            value = None
    _metadata_cache[path] = value
    return value


# ------------------------------------------------------ preemption notice
# Spot/preemptible TPU-VMs get ~30s of warning: GCE flips the
# instance/preempted metadata attribute (and delivers the ACPI G2 soft
# off) before the hard kill. Serving replicas poll this channel and
# drain instead of dying mid-stream (serve/replica.py); chaos tests
# inject the notice without a cloud via the env/file hooks below.
PREEMPT_TEST_ENV = "RAY_TPU_TESTING_PREEMPTED"
PREEMPT_TEST_FILE_ENV = "RAY_TPU_TESTING_PREEMPT_FILE"


def preemption_watch_enabled() -> bool:
    """Whether polling for preemption notices can ever observe one:
    on GCE, or when a chaos injection hook is armed."""
    return bool(os.environ.get(PREEMPT_TEST_ENV)
                or os.environ.get(PREEMPT_TEST_FILE_ENV)
                or _on_gce())


def check_preemption_notice() -> bool:
    """True once the platform announced this VM is being preempted.
    Deliberately NOT cached (unlike _gce_metadata) — the whole point is
    observing the flip; callers poll on a ~1s cadence. Chaos channels
    are checked first: the env flag arms a whole process at spawn, the
    marker file lets a test flip a LIVE replica from outside."""
    if os.environ.get(PREEMPT_TEST_ENV):
        return True
    marker = os.environ.get(PREEMPT_TEST_FILE_ENV)
    if marker:
        return os.path.exists(marker)
    if not _on_gce():
        return False
    try:
        import urllib.request
        req = urllib.request.Request(
            _GCE_METADATA_URL + "instance/preempted",
            headers={"Metadata-Flavor": "Google"})
        with urllib.request.urlopen(req, timeout=_GCE_TIMEOUT_S) as r:
            return r.read().decode().strip().upper() == "TRUE"
    except Exception:
        return False


def _count_device_nodes() -> int:
    """Chips attached to this host, by their device nodes: /dev/accel*
    (v2-v4) or one /dev/vfio/<n> each beside the /dev/vfio/vfio control
    node (v5e and later). chip_smoke.py compares what the node advertises
    from this with jax.device_count() on the machine it runs on."""
    n = len(glob.glob("/dev/accel*"))
    if n == 0:
        n = len([p for p in glob.glob("/dev/vfio/*")
                 if os.path.basename(p) != "vfio"])
    return n


def processes_holding_chips() -> List[int]:
    """PIDs that have one of this host's chip device nodes open — the
    processes a chip belongs to right now. Reads /proc, needs no JAX."""
    held = []
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            fds = os.listdir(f"/proc/{pid}/fd")
        except OSError:
            continue    # gone, or not ours to read
        for fd in fds:
            try:
                target = os.readlink(f"/proc/{pid}/fd/{fd}")
            except OSError:
                continue
            if target.startswith("/dev/accel") or (
                    target.startswith("/dev/vfio/")
                    and os.path.basename(target) != "vfio"):
                held.append(int(pid))
                break
    return held


class TPUAcceleratorManager(AcceleratorManager):
    @staticmethod
    def get_resource_name() -> str:
        return "TPU"

    @staticmethod
    def get_visible_accelerator_ids_env_var() -> str:
        return TPU_VISIBLE_CHIPS_ENV

    @staticmethod
    def get_current_node_num_accelerators() -> int:
        visible = TPUAcceleratorManager.get_current_process_visible_accelerator_ids()
        if visible is not None:
            return len(visible)
        n = _count_device_nodes()
        if n == 0:
            # no device nodes visible (some TPU-VM images mount them
            # late): infer the per-host chip count from the detected
            # accelerator type so unattended bring-up still advertises TPU
            accel = TPUAcceleratorManager.get_current_node_accelerator_type()
            if accel:
                n = _chips_on_this_host(accel)
        if n == 0 and os.environ.get("RAY_TPU_FAKE_CHIPS"):
            n = int(os.environ["RAY_TPU_FAKE_CHIPS"])
        return n

    @staticmethod
    def get_current_node_accelerator_type() -> Optional[str]:
        # autodetect first (GCE metadata, short timeout, cached), env last
        # — a real TPU-VM then works unattended with no env setup
        return (_gce_metadata("instance/attributes/accelerator-type")
                or os.environ.get(GCE_TPU_ACCEL_TYPE_ENV))

    @staticmethod
    def get_current_process_visible_accelerator_ids() -> Optional[List[str]]:
        v = os.environ.get(TPU_VISIBLE_CHIPS_ENV)
        if v is None or v == "":
            return None
        return [x for x in v.split(",") if x != ""]

    @staticmethod
    def set_current_process_visible_accelerator_ids(ids: List[str]) -> None:
        """Grant this process exactly the chips `ids`, and let JAX open
        the platform the node was started with."""
        ids = [str(i) for i in ids]
        os.environ[TPU_VISIBLE_CHIPS_ENV] = ",".join(ids)
        bounds = _SUBHOST_BOUNDS.get(len(ids))
        if bounds and len(ids) < _count_device_nodes():
            os.environ[TPU_CHIPS_PER_HOST_BOUNDS_ENV] = bounds
            os.environ[TPU_HOST_BOUNDS_ENV] = "1,1,1"
        if _node_jax_platforms is not _NEVER_PINNED:
            _pin_jax_platforms(_node_jax_platforms)

    @staticmethod
    def hide_accelerators_from_current_process() -> None:
        """A process whose lease carries no chip may open only the CPU
        backend, so it can never take a chip from the lease that owns it."""
        _pin_jax_platforms("cpu")

    @staticmethod
    def get_current_node_tpu_pod_name() -> Optional[str]:
        return (_gce_metadata("instance/attributes/instance-id")
                or os.environ.get(GCE_TPU_NAME_ENV))

    @staticmethod
    def is_pod_worker_0() -> bool:
        wid = (_gce_metadata("instance/attributes/agent-worker-number")
               or os.environ.get(GCE_TPU_WORKER_ID_ENV, "0"))
        return wid == "0"

    @staticmethod
    def get_current_node_additional_resources() -> Dict[str, float]:
        """Slice resources: tpu-slice:{pod_name}: 1 on every slice
        host, TPU-{type}-head: 1 on worker 0 (reference:
        tpu.py:334-397)."""
        out: Dict[str, float] = {}
        accel_type = TPUAcceleratorManager.get_current_node_accelerator_type()
        pod_name = TPUAcceleratorManager.get_current_node_tpu_pod_name()
        if accel_type and _is_multi_host(accel_type):
            if pod_name:
                # prefixed so slice-membership markers are recognizable to
                # the gang scheduler (train/slice.py) among arbitrary
                # custom resources
                out[f"tpu-slice:{pod_name}"] = 1.0
            if TPUAcceleratorManager.is_pod_worker_0():
                out[f"TPU-{accel_type}-head"] = 1.0
        return out

    @staticmethod
    def validate_resource_request_quantity(quantity: float):
        if quantity not in (0,) and quantity > 0 and quantity != int(quantity):
            return (False, "TPU chips are not fractionally shareable")
        return (True, None)


def _is_multi_host(accel_type: str) -> bool:
    m = re.match(r"^[^-]+-(\d+)$", accel_type)
    if not m:
        return False
    return int(m.group(1)) > _chips_per_host(accel_type)


def slice_hosts(accel_type: str) -> int:
    """Number of hosts in a slice, e.g. v4-32 -> 4 (v4: 2 chips/core-count
    unit; core count 32 -> 16 chips -> 4 hosts of 4 chips)."""
    m = re.match(r"^v(\d+)[a-z]*-(\d+)$", accel_type)
    if not m:
        return 1
    gen = accel_type.split("-")[0]
    count = int(accel_type.split("-")[-1])
    if gen in ("v2", "v3", "v4", "v5p"):   # N = core count, 2 cores/chip
        chips = count // 2
    else:                                   # v5litepod/v6e: N = chip count
        chips = count
    return max(1, chips // _chips_per_host(accel_type))

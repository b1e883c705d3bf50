"""veles_tpu — a TPU-native dataflow deep-learning framework.

A ground-up rebuild of the capabilities of Samsung VELES (the reference at
/root/reference; see SURVEY.md) designed for TPUs: models are Workflows of
linked Units, but the per-minibatch compute compiles to a single jitted XLA
SPMD step over a ``jax.sharding.Mesh`` instead of per-unit kernel dispatch,
and distributed data parallelism is ``psum`` over ICI instead of a ZeroMQ
master–slave parameter server.
"""

__version__ = "0.1.0"

import os as _os

_chips = _os.environ.get("TPU_VISIBLE_CHIPS")
if _chips:
    # mesh_slice_placement contract honored on the host platform too:
    # a trial child placed on a d-chip slice materializes exactly d
    # virtual CPU devices, however the CPU backend ends up selected
    # (env pin here, or --backend cpu later) — so slice-placement
    # correctness is CI-testable without multi-chip hardware
    # (parallel/trials.py). Harmless on a real TPU host, where the
    # runtime consumes TPU_VISIBLE_CHIPS natively and the CPU client
    # is never the training backend. The forced-host-device-count flag
    # would fight the setting — strip it before jax initializes.
    _flags = _os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in _flags:
        # the user set BOTH knobs: dropping their flag silently (and
        # exporting the stripped XLA_FLAGS to every child) would be a
        # mystery device-count change — say so (ADVICE r4)
        import warnings as _warnings
        _warnings.warn(
            "TPU_VISIBLE_CHIPS overrides xla_force_host_platform_"
            "device_count: stripping the flag from XLA_FLAGS (the "
            "slice-placement contract owns the CPU device count; "
            "unset TPU_VISIBLE_CHIPS to keep your flag)",
            RuntimeWarning, stacklevel=2)
    _os.environ["XLA_FLAGS"] = " ".join(
        t for t in _flags.split()
        if "xla_force_host_platform_device_count" not in t)
    import jax as _jax

    _jax.config.update("jax_num_cpu_devices", len(
        [c for c in _chips.split(",") if c.strip() != ""]))

from .config import root                              # noqa: F401
from .error import (VelesError, Bug, NoMoreJobs)      # noqa: F401
from .mutable import Bool, LinkableAttribute, link    # noqa: F401
from .units import Unit, UnitRegistry, TrivialUnit    # noqa: F401
from .workflow import Workflow                        # noqa: F401
from .plumbing import (StartPoint, EndPoint, Repeater,
                       FireStarter)                   # noqa: F401
from .memory import Array, Watcher                    # noqa: F401
from .backends import (Device_for, XLADevice, NumpyDevice,
                       make_mesh)                     # noqa: F401
from .accelerated import (AcceleratedUnit,
                          AcceleratedWorkflow)        # noqa: F401
from .snapshotter import (Snapshotter, SnapshotterToDB, load_snapshot,
                          resume, collect_state,
                          apply_state)                # noqa: F401
from .mean_disp_normalizer import MeanDispNormalizer  # noqa: F401
from .input_joiner import InputJoiner                 # noqa: F401
from .avatar import Avatar                            # noqa: F401
from . import normalization                           # noqa: F401
from . import prng                                    # noqa: F401
from .plotter import Plotter, PlotSink                # noqa: F401
from .plotting_units import (AccumulatingPlotter, MatrixPlotter,
                             ImagePlotter, Histogram, MultiHistogram,
                             TableMaxMin, StepStats)  # noqa: F401
from .restful_api import GenerationAPI, RESTfulAPI    # noqa: F401
from . import overlap                                 # noqa: F401
from .overlap import Prefetcher, SidePlane            # noqa: F401
from . import resilience                              # noqa: F401
from .resilience import (RetryPolicy, FaultInjected,
                         SnapshotCorruptError)        # noqa: F401
from .publishing import Publisher                     # noqa: F401
from .interaction import Shell                        # noqa: F401
from .json_encoders import NumpyJSONEncoder           # noqa: F401

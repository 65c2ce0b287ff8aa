"""Test configuration: force CPU backend with 8 virtual devices so sharding
logic is testable without a TPU pod (SURVEY.md §4: FakeCommBackend analog)."""
import os

# Must happen before jax (via paddle_tpu) initializes a backend. Force cpu
# whatever the environment says: correctness CI runs on the host — a chip,
# where there is one, stays free for the one process that may hold it
# (chip_smoke.py, tests_tpu/), and matmuls are exact f32 instead of
# TPU-default bf16.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

# The CPU client runs the virtual devices' executions on ONE thread pool of
# max(PJRT_NPROC or cores, devices) threads. With eight devices on eight
# cores, executions of the NEXT eagerly dispatched program can hold threads
# that a collective's missing participants need: XLA's rendezvous then
# aborts the process after 40 s ("Termination timeout"), which took an
# xdist worker down in tests/test_semi_auto_llama.py in 7 of 48 runs under
# the suite's load (0 of 18 with room for four programs in flight).
os.environ.setdefault("PJRT_NPROC", "32")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    # the tier-1 invocation deselects these (-m 'not slow'); registering
    # the marker makes that contract explicit instead of an unknown-mark
    # warning
    config.addinivalue_line(
        "markers",
        "slow: multi-process flagship drills excluded from the tier-1 "
        "run (-m 'not slow'); run them explicitly with -m slow")

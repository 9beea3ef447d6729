import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The lines above MUST run before any jax-touching import: jax locks
# the device count at first backend initialization, and the production
# meshes below need 512 placeholder host devices. They are CPU devices,
# so this process never opens a chip.

from repro.launch.dryrun_lib import main  # noqa: E402

if __name__ == "__main__":
    main()

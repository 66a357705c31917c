"""Roofline CLI on the host-CPU fake fleet: importing repro.launch.dryrun
first pins JAX to the CPU and sets the fleet's device count, before jax
initializes a backend."""
from repro.launch import dryrun  # noqa: F401
from repro.launch.roofline import main

if __name__ == "__main__":
    main()

"""Packaging for quiver_tpu (reference setup.py builds the CUDA extension;
here the native piece is the plain-C-ABI host engine, compiled by a custom
build step with no pybind11/torch involvement)."""

import os
import subprocess

from setuptools import find_packages, setup
from setuptools.command.build_py import build_py


class BuildWithNative(build_py):
    def run(self):
        csrc = os.path.join(os.path.dirname(os.path.abspath(__file__)), "quiver_tpu", "csrc")
        if os.path.exists(os.path.join(csrc, "Makefile")):
            try:
                subprocess.run(["make", "-C", csrc], check=True)
            except (OSError, subprocess.CalledProcessError) as e:
                # not fatal at install time: the library is rebuilt from
                # the shipped source on first use (ops/cpu_kernels.py),
                # and HOST-mode sampling refuses to run without it
                print(f"warning: native build failed ({e}); it is retried on first use")
        super().run()


setup(
    name="quiver-tpu",
    version="0.1.0",
    description="TPU-native graph-learning data engine (torch-quiver capabilities on JAX/XLA)",
    packages=find_packages(include=["quiver_tpu", "quiver_tpu.*", "quiver"]),
    package_data={"quiver_tpu": ["csrc/*.so", "csrc/*.cpp", "csrc/Makefile"]},
    python_requires=">=3.10",
    # the versions the code is written and tested against
    install_requires=["jax==0.9.0", "jaxlib==0.9.0", "flax==0.12.3", "optax==0.2.6", "numpy"],
    cmdclass={"build_py": BuildWithNative},
)

# Control-plane image: HTTP + gRPC APIs, orchestrator, Kubernetes backend.
#
# Reference parity: Dockerfile (poetry venv builder + slim runtime with
# kubectl, /storage prepared, `python -m code_interpreter` entrypoint).
# Simplifications: no poetry (plain pip install of the package), kubectl
# fetched from the official dl endpoint instead of an OS package.
#
# Build from the repo root:  docker build -t tpu-code-interpreter .

FROM python:3.12-slim-bookworm

ARG KUBECTL_VERSION=v1.31.0
ARG TARGETARCH=amd64
ADD https://dl.k8s.io/release/${KUBECTL_VERSION}/bin/linux/${TARGETARCH}/kubectl /usr/local/bin/kubectl
RUN chmod 0755 /usr/local/bin/kubectl

WORKDIR /app
COPY pyproject.toml README.md ./
COPY bee_code_interpreter_fs_tpu ./bee_code_interpreter_fs_tpu
COPY proto ./proto
RUN pip install --no-cache-dir .

# Shared file storage; chmod 777 so arbitrary-UID clusters can write
# (reference Dockerfile:21).
RUN mkdir -p /storage && chmod 777 /storage
# The compile cache is placed from outside (config.jax_cache_dir): executor
# pods mount their cache volume at this path, the one executor/Dockerfile
# names too.
ENV APP_FILE_STORAGE_PATH=/storage \
    APP_EXECUTOR_BACKEND=kubernetes \
    JAX_COMPILATION_CACHE_DIR=/var/tmp/tpu-code-interpreter/jax-cache

EXPOSE 8000 50051
ENTRYPOINT ["python", "-m", "bee_code_interpreter_fs_tpu"]

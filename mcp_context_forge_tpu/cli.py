"""CLI entry point (reference: mcpgateway/cli.py uvicorn launcher).

Subcommands: serve (default), supervise, token (mint an admin JWT),
version.

One process per chip: nothing here imports jax. ``serve`` reaches it only
inside ``build_app`` (engine construction), and ``supervise`` never does —
its workers are spawned ``serve`` processes, so the parent holds no
accelerator a worker could then fail to open. The platform is JAX's own
business (``JAX_PLATFORMS``)."""

from __future__ import annotations

import argparse
import sys


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="mcpforge",
                                     description="TPU-native MCP gateway")
    sub = parser.add_subparsers(dest="command")

    serve = sub.add_parser("serve", help="run the gateway")
    serve.add_argument("--host", default=None)
    serve.add_argument("--port", type=int, default=None)

    supervise = sub.add_parser(
        "supervise", help="run N worker processes + coordination hub "
                          "(reference: gunicorn multi-worker)")
    supervise.add_argument("--workers", type=int, default=2)
    supervise.add_argument("--host", default=None)
    supervise.add_argument("--port", type=int, default=None,
                           help="shared SO_REUSEPORT port (default), or the "
                                "base port with --port-per-worker")
    supervise.add_argument("--hub-port", type=int, default=None,
                           help="coordination hub port (default: base port-1)")
    supervise.add_argument("--no-hub", action="store_true",
                           help="workers use an external bus (no embedded hub)")
    supervise.add_argument("--port-per-worker", action="store_true",
                           help="legacy layout: worker i listens on port+i "
                                "behind an external LB instead of one "
                                "SO_REUSEPORT socket")
    supervise.add_argument("--pin-cpus", action="store_true",
                           help="pin worker i to cpu i%%ncpus "
                                "(sched_setaffinity; Linux only, opt-in — "
                                "helps only when workers <= free cores)")

    token = sub.add_parser("token", help="mint a JWT for an email")
    token.add_argument("email")
    token.add_argument("--expires-minutes", type=int, default=60)

    sub.add_parser("version", help="print version")

    args = parser.parse_args(argv)
    command = args.command or "serve"

    if command == "version":
        from . import __version__
        print(__version__)
        return 0

    from .config import get_settings
    settings = get_settings()

    if command == "token":
        from .utils import jwt
        print(jwt.create_token({"sub": args.email}, settings.jwt_secret_key,
                               settings.jwt_algorithm,
                               expires_minutes=args.expires_minutes,
                               audience=settings.jwt_audience,
                               issuer=settings.jwt_issuer))
        return 0

    if command == "serve":
        if args.host:
            settings = settings.model_copy(update={"host": args.host})
        if args.port:
            settings = settings.model_copy(update={"port": args.port})
        from .gateway.app import run
        run(settings)
        return 0

    if command == "supervise":
        from .supervisor import Supervisor
        base_port = args.port or settings.port
        supervisor = Supervisor(
            workers=args.workers, host=args.host or settings.host,
            base_port=base_port,
            hub_port=None if args.no_hub else (args.hub_port or base_port - 1),
            reuse_port=not args.port_per_worker,
            pin_cpus=args.pin_cpus)
        supervisor.run_forever()
        return 0

    parser.print_help()
    return 1


if __name__ == "__main__":
    sys.exit(main())

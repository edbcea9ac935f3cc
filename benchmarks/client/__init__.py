"""The benchmark's load generator: participants that join over `/rtc`, publish
and subscribe over sealed UDP, in processes that never import JAX or
`livekit_server_tpu`. A copy of what `chip_smoke.py` and the server's
`runtime/crypto.py`, `auth/token.py` and `runtime/udp.py` say the wire is."""

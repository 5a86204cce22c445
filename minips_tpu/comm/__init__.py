"""The wire fleet's message layer: buses, framing, heartbeats, and the
optional chaos / reliable layers that ``make_bus`` imports lazily.

Nothing is re-exported: a name is imported from its module
(``comm.bus.ControlBus``, ``comm.heartbeat.HeartbeatMonitor``), so
importing ``comm.framing`` starts no zmq.
"""

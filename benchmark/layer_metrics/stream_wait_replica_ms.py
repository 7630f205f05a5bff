"""Mean wait, in ms, of the streamed items that are not the engine's own:
every stream of the process (`stream_wait_s`, `stream_items_taken`) less the
engine's `generate_stream` group (`engine_stream_*`), over the window. In a
Serve deployment what is left is the replica's token stream, taken by the
proxy's event loop."""


def read(collected):
    window = collected["engine_window"]
    wait_s = window["stream_wait_s"] - window["engine_stream_wait_s"]
    items = window["stream_items_taken"] - window["engine_stream_items_taken"]
    return 1000.0 * wait_s / items

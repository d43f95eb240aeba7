"""Items (tokens or images; the configuration's ``item`` says which) in
all whole blocks completed in the window, over the host-clock time from
the window's start to the last block's fetched loss.  Global, over all
chips of the cell."""

UNIT = 'items/s'


def read(run):
    if not run.get('window_seconds'):
        return None
    return run['items_done'] / run['window_seconds']

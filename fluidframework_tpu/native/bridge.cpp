// Front-door socket bridge — native framed-TCP transport.
//
// Reference parity: the native transport layer under the reference's
// front door — Node's libuv socket machinery + socket.io/ws native
// addons carrying alfred's connections (alfred/index.ts:343,
// driver-base documentDeltaConnection.ts:35) — and SURVEY.md §2.9/§5.8's
// "C++ streaming bridge between the front door and the TPU host": the
// DCN hop is owned by native code; Python only sees whole decoded
// frames.
//
// Threads: one acceptor, one reader per connection (blocking reads of
// whole frames), and a small fixed pool of writers (kWriters), each
// draining the outboxes of its share of the connections with
// non-blocking sends and poll() — so a host of thousands of connections
// holds about one thread per connection, and a peer that stops reading
// stalls only its own outbox. A body sent to many connections in a row
// (one broadcast to a document's sessions) is framed once and shared by
// their outboxes.
//
// Protocol: the same length-prefixed framing alfred speaks (4-byte BE
// length + body), so the existing network driver connects to this
// bridge unchanged. The C++ side owns accept/read/write threads and
// per-connection outboxes; the host pumps events:
//
//   bridge_start(port) -> handle          bridge_port(handle)
//   bridge_next_size(handle)              size of next event payload
//   bridge_poll_wait(handle, timeout_ms)  block until an event is queued;
//       returns its size, or -3 on timeout (cv wait, no busy polling)
//   bridge_poll(handle, buf, cap)         -> [conn:8B][kind:4B][body...]
//       kind: 0 = OPEN, 1 = DATA (body = one frame), 2 = CLOSE
//   bridge_send(handle, conn, data, len)  enqueue one framed body
//       (0 ok, -1 unknown/closing, -2 outbox full — caller should close)
//   bridge_set_max_outbox(handle, n)      tune the -2 threshold
//   bridge_set_conn_max_outbox(handle, conn, n)  per-connection override
//       (connection classes: viewers shallow, writers default)
//   bridge_close(handle, conn)            server-side disconnect
//   bridge_stop(handle)
//
// Backpressure: a connection whose decoded frames pile up faster than
// the host pump drains them (kMaxInboundQueue) is dropped, and a peer
// that stops reading until kMaxOutbox responses queue up gets -2 from
// bridge_send — mirroring socket.io/Redis adapter slow-consumer drops;
// kMaxFrame alone only bounds a single frame.
//
// Exposed as a C ABI for ctypes (bridge.py).

#include <arpa/inet.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <poll.h>
#include <sys/socket.h>
#include <unistd.h>

#include <cerrno>
#include <atomic>
#include <chrono>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <deque>
#include <map>
#include <memory>
#include <mutex>
#include <string>
#include <thread>
#include <vector>

namespace {

constexpr uint32_t kMaxFrame = 64u * 1024u * 1024u;
constexpr size_t kMaxInboundQueue = 8192;  // decoded frames per conn
constexpr size_t kMaxOutbox = 8192;        // queued responses per conn
constexpr int kWriters = 4;                // writer threads per bridge

struct Event {
    int64_t conn;
    int32_t kind;  // 0 open, 1 data, 2 close
    std::string body;
};

struct Writer;

struct Conn {
    int fd = -1;
    std::thread reader;
    Writer* writer = nullptr;
    std::mutex out_mu;
    // Framed bodies (length word first), shared across connections.
    std::deque<std::shared_ptr<const std::string>> outbox;
    // Per-connection outbox bound; 0 = use the bridge-wide default.
    // Lets connection CLASSES differ (a read-only viewer lag-drops at a
    // shallow outbox while writer connections keep the deep default).
    size_t max_outbox = 0;
    bool closing = false;
    bool scheduled = false;  // in its writer's ready list (writer->mu)
    // Owned by the writer thread: the frame being sent and how far.
    bool active = false;
    std::shared_ptr<const std::string> cur;
    size_t off = 0;

    ~Conn() {
        if (fd >= 0) ::close(fd);
    }
};

// One writer thread: connections with queued frames arrive in ``ready``;
// it sends until each would block, then polls those for POLLOUT.
struct Writer {
    std::mutex mu;
    std::condition_variable cv;
    std::vector<std::shared_ptr<Conn>> ready;
    bool stop = false;
    std::thread thread;
};

struct Bridge {
    int listen_fd = -1;
    int port = 0;
    // Outbox bound (kMaxOutbox default); tunable so hosts/tests can pick
    // the point where a stalled reader trips -2 instead of buffering on.
    std::atomic<size_t> max_outbox{kMaxOutbox};
    std::atomic<bool> stopping{false};
    std::thread acceptor;
    std::mutex mu;  // guards conns, events, inbound_depth
    std::condition_variable events_cv;
    std::map<int64_t, std::shared_ptr<Conn>> conns;
    Writer writers[kWriters];
    // The last body framed by bridge_send and where it was read from: the
    // next send of the same bytes shares it instead of copying (mu).
    std::shared_ptr<const std::string> last_frame;
    const char* last_src = nullptr;
    int64_t next_conn = 1;
    std::deque<Event> events;
    std::map<int64_t, size_t> inbound_depth;  // queued DATA events per conn
    // Detached per-close reapers; stop() waits for the count to drain
    // before freeing the Bridge (their Conn readers touch b->events).
    std::mutex reap_mu;
    std::condition_variable reap_cv;
    int live_reapers = 0;
};

bool read_exact(int fd, char* buf, size_t n) {
    size_t got = 0;
    while (got < n) {
        ssize_t r = ::recv(fd, buf + got, n - got, 0);
        if (r <= 0) return false;
        got += static_cast<size_t>(r);
    }
    return true;
}


void reader_loop(Bridge* b, int64_t id, int fd) {
    for (;;) {
        char header[4];
        if (!read_exact(fd, header, 4)) break;
        uint32_t len = (static_cast<uint8_t>(header[0]) << 24)
                       | (static_cast<uint8_t>(header[1]) << 16)
                       | (static_cast<uint8_t>(header[2]) << 8)
                       | static_cast<uint8_t>(header[3]);
        if (len > kMaxFrame) break;
        std::string body(len, '\0');
        if (len && !read_exact(fd, &body[0], len)) break;
        {
            std::lock_guard<std::mutex> lock(b->mu);
            // Backpressure: drop the connection rather than buffer a
            // sender that outruns the pump without bound.
            if (b->inbound_depth[id] >= kMaxInboundQueue) break;
            ++b->inbound_depth[id];
            b->events.push_back(Event{id, 1, std::move(body)});
        }
        b->events_cv.notify_one();
    }
    {
        std::lock_guard<std::mutex> lock(b->mu);
        b->events.push_back(Event{id, 2, std::string()});
    }
    b->events_cv.notify_one();
}

// Send ``c``'s queued frames until its socket would block (1), the
// outbox is empty (0), or the connection is closing or broken (-1: the
// reader sees the peer go and raises CLOSE).
int flush_conn(Conn* c) {
    for (;;) {
        if (!c->cur || c->off == c->cur->size()) {
            std::lock_guard<std::mutex> lock(c->out_mu);
            if (c->closing) return -1;
            if (c->outbox.empty()) {
                c->cur.reset();
                c->off = 0;
                return 0;
            }
            c->cur = std::move(c->outbox.front());
            c->outbox.pop_front();
            c->off = 0;
        }
        ssize_t r = ::send(c->fd, c->cur->data() + c->off,
                           c->cur->size() - c->off,
                           MSG_NOSIGNAL | MSG_DONTWAIT);
        if (r > 0) {
            c->off += static_cast<size_t>(r);
        } else if (r < 0 && (errno == EAGAIN || errno == EWOULDBLOCK
                             || errno == EINTR)) {
            return 1;
        } else {
            return -1;
        }
    }
}

void writer_loop(Writer* w) {
    std::vector<std::shared_ptr<Conn>> active;
    std::vector<pollfd> blocked;
    for (;;) {
        {
            std::unique_lock<std::mutex> lock(w->mu);
            if (active.empty())
                w->cv.wait(lock,
                           [w] { return w->stop || !w->ready.empty(); });
            if (w->stop) return;
            for (auto& c : w->ready) {
                c->scheduled = false;
                if (!c->active) {
                    c->active = true;
                    active.push_back(std::move(c));
                }
            }
            w->ready.clear();
        }
        blocked.clear();
        size_t keep = 0;
        for (auto& c : active) {
            if (flush_conn(c.get()) == 1) {
                blocked.push_back(pollfd{c->fd, POLLOUT, 0});
                active[keep++] = std::move(c);
            } else {
                c->active = false;  // rescheduled by its next send
            }
        }
        active.resize(keep);
        // Bounded wait: frames queued meanwhile for other connections
        // are picked up within a few ms.
        if (!blocked.empty())
            ::poll(blocked.data(), blocked.size(), 5);
    }
}

void accept_loop(Bridge* b) {
    while (!b->stopping.load()) {
        int fd = ::accept(b->listen_fd, nullptr, nullptr);
        if (fd < 0) {
            if (b->stopping.load()) return;
            // EMFILE etc.: back off instead of spinning a core.
            std::this_thread::sleep_for(std::chrono::milliseconds(10));
            continue;
        }
        int one = 1;
        ::setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
        auto conn = std::make_shared<Conn>();
        conn->fd = fd;
        std::lock_guard<std::mutex> lock(b->mu);
        int64_t id = b->next_conn++;
        conn->writer = &b->writers[id % kWriters];
        conn->reader = std::thread(reader_loop, b, id, fd);
        b->conns[id] = std::move(conn);
        b->events.push_back(Event{id, 0, std::string()});
        b->events_cv.notify_one();
    }
}

// Stop ``c``: its writer drops it at the next pass, its reader ends;
// the fd closes with the last reference (~Conn), never under a send.
void shutdown_conn(Conn* c) {
    {
        std::lock_guard<std::mutex> lock(c->out_mu);
        c->closing = true;
    }
    ::shutdown(c->fd, SHUT_RDWR);
    if (c->reader.joinable()) c->reader.join();
}

}  // namespace

extern "C" {

void* bridge_start(int port) {
    auto b = std::make_unique<Bridge>();
    b->listen_fd = ::socket(AF_INET, SOCK_STREAM, 0);
    if (b->listen_fd < 0) return nullptr;
    int one = 1;
    ::setsockopt(b->listen_fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
    sockaddr_in addr{};
    addr.sin_family = AF_INET;
    addr.sin_addr.s_addr = htonl(INADDR_LOOPBACK);
    addr.sin_port = htons(static_cast<uint16_t>(port));
    if (::bind(b->listen_fd, reinterpret_cast<sockaddr*>(&addr),
               sizeof(addr)) != 0
        || ::listen(b->listen_fd, 64) != 0) {
        ::close(b->listen_fd);
        return nullptr;
    }
    socklen_t len = sizeof(addr);
    ::getsockname(b->listen_fd, reinterpret_cast<sockaddr*>(&addr), &len);
    b->port = ntohs(addr.sin_port);
    for (Writer& w : b->writers) w.thread = std::thread(writer_loop, &w);
    b->acceptor = std::thread(accept_loop, b.get());
    return b.release();
}

int bridge_port(void* handle) {
    return static_cast<Bridge*>(handle)->port;
}

int64_t bridge_next_size(void* handle) {
    Bridge* b = static_cast<Bridge*>(handle);
    std::lock_guard<std::mutex> lock(b->mu);
    if (b->events.empty()) return -3;
    return static_cast<int64_t>(12 + b->events.front().body.size());
}

int64_t bridge_poll_wait(void* handle, int timeout_ms) {
    Bridge* b = static_cast<Bridge*>(handle);
    std::unique_lock<std::mutex> lock(b->mu);
    b->events_cv.wait_for(lock, std::chrono::milliseconds(timeout_ms),
                          [b] { return !b->events.empty(); });
    if (b->events.empty()) return -3;
    return static_cast<int64_t>(12 + b->events.front().body.size());
}

int64_t bridge_poll(void* handle, char* buf, int64_t cap) {
    Bridge* b = static_cast<Bridge*>(handle);
    std::lock_guard<std::mutex> lock(b->mu);
    if (b->events.empty()) return -3;
    Event& event = b->events.front();
    int64_t need = static_cast<int64_t>(12 + event.body.size());
    if (need > cap) return -2;
    std::memcpy(buf, &event.conn, 8);
    std::memcpy(buf + 8, &event.kind, 4);
    if (!event.body.empty())
        std::memcpy(buf + 12, event.body.data(), event.body.size());
    if (event.kind == 1) {
        auto depth = b->inbound_depth.find(event.conn);
        if (depth != b->inbound_depth.end() && depth->second > 0)
            --depth->second;
    } else if (event.kind == 2) {
        b->inbound_depth.erase(event.conn);
    }
    b->events.pop_front();
    return need;
}

int bridge_send(void* handle, int64_t conn, const char* data,
                uint32_t len) {
    Bridge* b = static_cast<Bridge*>(handle);
    std::lock_guard<std::mutex> lock(b->mu);
    auto it = b->conns.find(conn);
    if (it == b->conns.end()) return -1;
    Conn* c = it->second.get();
    {
        std::lock_guard<std::mutex> out_lock(c->out_mu);
        if (c->closing) return -1;
        size_t limit = c->max_outbox ? c->max_outbox
                                     : b->max_outbox.load();
        if (c->outbox.size() >= limit) return -2;
        // The same bytes as the last send share its frame; the compare
        // (not the address alone) makes a reused buffer safe.
        const std::string* last = b->last_frame.get();
        if (!(last && b->last_src == data && last->size() == 4 + len
              && std::memcmp(last->data() + 4, data, len) == 0)) {
            auto framed = std::make_shared<std::string>(
                4 + static_cast<size_t>(len), '\0');
            (*framed)[0] = static_cast<char>((len >> 24) & 0xFF);
            (*framed)[1] = static_cast<char>((len >> 16) & 0xFF);
            (*framed)[2] = static_cast<char>((len >> 8) & 0xFF);
            (*framed)[3] = static_cast<char>(len & 0xFF);
            if (len) std::memcpy(&(*framed)[4], data, len);
            b->last_frame = std::move(framed);
            b->last_src = data;
        }
        c->outbox.push_back(b->last_frame);
    }
    Writer* w = c->writer;
    {
        std::lock_guard<std::mutex> w_lock(w->mu);
        if (c->scheduled) return 0;
        c->scheduled = true;
        w->ready.push_back(it->second);
    }
    w->cv.notify_one();
    return 0;
}

void bridge_set_max_outbox(void* handle, int64_t n) {
    if (n > 0)
        static_cast<Bridge*>(handle)->max_outbox.store(
            static_cast<size_t>(n));
}

// Per-connection override of the -2 threshold (n <= 0 restores the
// bridge default). Returns 0, or -1 for an unknown connection.
int bridge_set_conn_max_outbox(void* handle, int64_t conn, int64_t n) {
    Bridge* b = static_cast<Bridge*>(handle);
    std::lock_guard<std::mutex> lock(b->mu);
    auto it = b->conns.find(conn);
    if (it == b->conns.end()) return -1;
    Conn* c = it->second.get();
    std::lock_guard<std::mutex> out_lock(c->out_mu);
    c->max_outbox = n > 0 ? static_cast<size_t>(n) : 0;
    return 0;
}

int bridge_close(void* handle, int64_t conn) {
    Bridge* b = static_cast<Bridge*>(handle);
    std::shared_ptr<Conn> owned;
    {
        std::lock_guard<std::mutex> lock(b->mu);
        auto it = b->conns.find(conn);
        if (it == b->conns.end()) return -1;
        owned = std::move(it->second);
        b->conns.erase(it);
    }
    // Joining the reader can block on in-flight IO; do it off the
    // caller's thread (detached) so the Python pump never stalls and no
    // unjoined thread accumulates per disconnect.
    {
        std::lock_guard<std::mutex> lock(b->reap_mu);
        ++b->live_reapers;
    }
    std::thread([b, owned] {
        shutdown_conn(owned.get());
        {
            std::lock_guard<std::mutex> lock(b->reap_mu);
            --b->live_reapers;
        }
        b->reap_cv.notify_all();
    }).detach();
    return 0;
}

void bridge_stop(void* handle) {
    Bridge* b = static_cast<Bridge*>(handle);
    b->stopping.store(true);
    ::shutdown(b->listen_fd, SHUT_RDWR);
    ::close(b->listen_fd);
    if (b->acceptor.joinable()) b->acceptor.join();
    std::map<int64_t, std::shared_ptr<Conn>> conns;
    {
        std::lock_guard<std::mutex> lock(b->mu);
        conns.swap(b->conns);
    }
    for (auto& entry : conns) shutdown_conn(entry.second.get());
    for (Writer& w : b->writers) {
        {
            std::lock_guard<std::mutex> lock(w.mu);
            w.stop = true;
        }
        w.cv.notify_all();
        if (w.thread.joinable()) w.thread.join();
    }
    {
        std::unique_lock<std::mutex> lock(b->reap_mu);
        b->reap_cv.wait(lock, [b] { return b->live_reapers == 0; });
    }
    delete b;
}

}  // extern "C"
